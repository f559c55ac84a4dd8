"""Storage census / reference audit / integrity scrub (PR 16).

Fixtures build a REAL storage root through the production write
paths — ``ChunkStore.put`` + ``RecipeStore.publish`` for the chunk/
pack/recipe planes, plain CAS writes for blobs, ``ManifestStore``
layout for manifests — then measure, break, and re-measure it.
"""

import hashlib
import json
import os
import shutil

import pytest

from conftest import cas_entry_path, cas_index_bytes

from makisu_tpu.cache import census as census_mod
from makisu_tpu.cache.census import IOBudget, StorageCensus
from makisu_tpu.cache.chunks import ChunkStore
from makisu_tpu.serve import recipe as recipe_mod
from makisu_tpu.utils import events, zstdio


def _pair(seed):
    from makisu_tpu.docker.image import (
        MEDIA_TYPE_LAYER, Descriptor, Digest, DigestPair)
    return DigestPair(
        tar_digest=Digest.from_hex(f"{seed:02x}" * 32),
        gzip_descriptor=Descriptor(
            MEDIA_TYPE_LAYER, 10,
            Digest.from_hex(f"{seed + 1:02x}" * 32)))


def _populate(tmp_path, tenant=""):
    """One published layer: two chunks, one pack (+zpack twin when
    zstd is available), one recipe; plus one blob + manifest pair.
    Returns (storage_dir, recipe_doc, fingerprints)."""
    storage = tmp_path / "storage"
    store = ChunkStore(str(storage / "chunks"))
    rs = recipe_mod.RecipeStore(str(storage / "serve"),
                                str(storage / "chunks"))
    c1, c2 = b"a" * 1000, b"b" * 3000
    fps = [hashlib.sha256(c).hexdigest() for c in (c1, c2)]
    for fp, data in zip(fps, (c1, c2)):
        store.put(fp, data)
    pair = _pair(0x10)
    doc = rs.publish(pair, [(0, 1000, fps[0]), (1000, 3000, fps[1])],
                     None, store)
    assert doc is not None

    blob_hex, config_hex = "cd" * 32, "ee" * 32
    for hx, size in ((blob_hex, 500), (config_hex, 80)):
        blob = cas_entry_path(storage / "layers", hx)
        os.makedirs(os.path.dirname(blob), exist_ok=True)
        with open(blob, "wb") as f:
            f.write(b"z" * size)
    man_dir = storage / "manifests" / "team" / "app"
    man_dir.mkdir(parents=True)
    (man_dir / "latest.json").write_text(json.dumps({
        "layers": [{"digest": f"sha256:{blob_hex}"}],
        "config": {"digest": f"sha256:{config_hex}"},
    }))
    if tenant:
        census_mod.record_attribution(
            str(storage), tenant,
            [doc["layer"]["tar"], blob_hex, config_hex])
    return str(storage), doc, fps


# -- census -------------------------------------------------------------------


def test_census_totals_match_disk(tmp_path):
    storage, doc, fps = _populate(tmp_path)
    out = StorageCensus(storage).census()
    assert out["schema"] == census_mod.CENSUS_SCHEMA
    assert out["planes"]["chunks"] == {
        "objects": 2, "bytes": 4000,
        "snapshots": 0, "snapshot_bytes": 0,
        "age": {"1h": 2, "1d": 0, "1w": 0, "30d": 0, "older": 0}}
    assert out["planes"]["blobs"]["objects"] == 2
    assert out["planes"]["blobs"]["bytes"] == 580
    assert out["planes"]["recipes"]["objects"] == 1
    packs = out["planes"]["packs"]
    assert packs["tables"] == 1
    # On-disk truth: every file the walk should count, counted once.
    want = 0
    for dirpath, _, files in os.walk(storage):
        if os.path.basename(dirpath) == "_tmp":
            continue
        for fn in files:
            if fn in (census_mod.CENSUS_CACHE_FILE,
                      census_mod.ATTRIBUTION_FILE) \
                    or "manifests" in dirpath:
                continue
            want += os.path.getsize(os.path.join(dirpath, fn))
    # Entries are counted, the layout's own index of them is not.
    want -= cas_index_bytes(os.path.join(storage, "chunks"))
    assert out["total_bytes"] == want
    # The cache file is the cheap-consumer path.
    totals = census_mod.cached_totals(storage)
    assert totals["total"] == out["total_bytes"]
    assert totals["chunks"] == 4000


def test_census_age_histogram_buckets(tmp_path):
    storage, _, fps = _populate(tmp_path)
    old = cas_entry_path(os.path.join(storage, "chunks"), fps[0])
    past = os.path.getmtime(old) - 40 * 86400
    os.utime(old, (past, past))
    out = StorageCensus(storage).census()
    age = out["planes"]["chunks"]["age"]
    assert age["older"] == 1 and age["1h"] == 1


def test_census_attribution_joins_tenant(tmp_path):
    storage, _, _ = _populate(tmp_path, tenant="team-a")
    out = StorageCensus(storage).census()
    tenants = out["tenants"]
    assert "team-a" in tenants
    # The recipe's chunks, pack objects, recipe file, and the blob all
    # charge to team-a; nothing else exists, so unattributed is absent.
    assert tenants["team-a"]["bytes"] == out["total_bytes"]
    assert census_mod.UNATTRIBUTED not in tenants


def test_census_unattributed_bucket(tmp_path):
    storage, _, _ = _populate(tmp_path)
    out = StorageCensus(storage).census()
    assert set(out["tenants"]) == {census_mod.UNATTRIBUTED}


def test_cap_label_folds_tail():
    assert census_mod.cap_label("") == census_mod.UNATTRIBUTED
    assert census_mod.cap_label("team-a", 0) == "team-a"
    assert census_mod.cap_label("team-z", 99) == \
        census_mod.TENANT_OVERFLOW
    assert len(census_mod.cap_label("x" * 200, 0)) == 64


def test_torn_attribution_sidecar_reads_empty(tmp_path):
    storage = tmp_path / "s"
    storage.mkdir()
    (storage / census_mod.ATTRIBUTION_FILE).write_text('{"layers": {"')
    assert census_mod.load_attribution(str(storage)) == {}


def test_cached_totals_absent_without_census(tmp_path):
    assert census_mod.cached_totals(str(tmp_path)) is None


# -- IO budget ----------------------------------------------------------------


def test_iobudget_oversized_object_admitted_alone():
    budget = IOBudget(max_resident_bytes=1024)
    budget.acquire(4096)  # larger than the whole budget: no deadlock
    assert budget.resident == 4096
    budget.release(4096)
    assert budget.resident == 0


def test_iobudget_reserve_is_balanced(tmp_path):
    budget = IOBudget(max_resident_bytes=1 << 20)
    big = tmp_path / "big"
    big.write_bytes(b"q" * (3 << 20))  # 3 pieces through a 1MiB budget
    digest, size = census_mod._hash_file(open(big, "rb"), budget)
    assert size == 3 << 20
    assert digest == hashlib.sha256(b"q" * (3 << 20)).hexdigest()
    assert budget.resident == 0


def test_iobudget_throttle_sleeps_over_limit(monkeypatch):
    naps = []
    monkeypatch.setattr(census_mod.time, "sleep", naps.append)
    budget = IOBudget(bytes_per_second=100)
    budget.throttle(50)
    assert not naps
    budget.throttle(200)
    assert naps and naps[0] > 0


# -- reference audit ----------------------------------------------------------


def test_audit_clean_store_has_no_findings(tmp_path):
    storage, _, _ = _populate(tmp_path)
    out = StorageCensus(storage).audit()
    assert out["findings"] == []
    assert out["classification"]["chunks"]["live"] == 2
    assert out["classification"]["chunks"]["orphaned"] == 0
    assert out["classification"]["recipes"]["live"] == 1
    assert out["classification"]["blobs"]["live"] == 2


def test_audit_names_dangling_chunk(tmp_path):
    storage, _, fps = _populate(tmp_path)
    os.unlink(cas_entry_path(os.path.join(storage, "chunks"), fps[0]))
    # A missing chunk whose pack survives as a compressed twin is
    # DEMOTED (recoverable), not dangling — remove the twin so the
    # loss is genuinely unrecoverable.
    shutil.rmtree(os.path.join(storage, "serve", "zpacks"),
                  ignore_errors=True)
    out = StorageCensus(storage).audit()
    kinds = {f["kind"] for f in out["findings"]}
    assert "dangling_chunk" in kinds
    assert "dangling_pack_member" in kinds
    dangling = next(f for f in out["findings"]
                    if f["kind"] == "dangling_chunk")
    assert dangling["chunk"] == fps[0]
    assert dangling["severity"] == "error"
    assert out["classification"]["recipes"]["dangling"] == 1
    assert out["classification"]["packs"]["dangling"] == 1


@pytest.mark.skipif(not zstdio.available(), reason="no zstd")
def test_audit_missing_chunk_with_twin_is_demoted(tmp_path):
    """A chunk absent from the CAS whose pack has a seekable twin is
    the budget evictor's expected footprint: classified demoted, zero
    findings — a post-eviction `doctor --storage` must exit clean."""
    storage, _, fps = _populate(tmp_path)
    os.unlink(cas_entry_path(os.path.join(storage, "chunks"), fps[0]))
    out = StorageCensus(storage).audit()
    assert out["findings"] == []
    assert out["classification"]["chunks"]["demoted"] == 1
    assert out["classification"]["recipes"]["dangling"] == 0
    assert out["classification"]["packs"]["dangling"] == 0


def test_audit_names_dangling_blob(tmp_path):
    storage, _, _ = _populate(tmp_path)
    blob_hex = "cd" * 32
    os.unlink(cas_entry_path(os.path.join(storage, "layers"), blob_hex))
    out = StorageCensus(storage).audit()
    dangling = [f for f in out["findings"]
                if f["kind"] == "dangling_blob"]
    assert [f["object"] for f in dangling] == [blob_hex]


def test_audit_corrupt_index_per_plane_never_crashes(tmp_path):
    """Satellite: mid-write truncation of each index plane (recipe
    JSON, pack table) must classify as corrupt_index — not crash."""
    storage, doc, _ = _populate(tmp_path)
    recipe_path = os.path.join(storage, "serve", "recipes",
                               f"{doc['layer']['gzip']}.json")
    pack_hex = doc["chunks"][0][2]
    table_path = os.path.join(storage, "serve", "packs",
                              f"{pack_hex}.json")
    for path in (recipe_path, table_path):
        whole = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(whole[:len(whole) // 2])  # torn mid-write
    census = StorageCensus(storage)
    out = census.audit()
    corrupt = [f for f in out["findings"]
               if f["kind"] == "corrupt_index"]
    assert {f["plane"] for f in corrupt} == {"recipes", "packs"}
    assert all(f["severity"] == "error" for f in corrupt)
    # The census survives the same torn files.
    census.census()


def test_audit_orphaned_zpack_and_repair(tmp_path):
    storage, _, _ = _populate(tmp_path)
    zdir = os.path.join(storage, "serve", "zpacks")
    os.makedirs(zdir, exist_ok=True)
    orphan_hex = "ab" * 32
    orphan = os.path.join(zdir, f"{orphan_hex}.zst")
    with open(orphan, "wb") as f:
        f.write(b"x" * 77)
    census = StorageCensus(storage)
    out = census.audit()
    found = [f for f in out["findings"]
             if f["kind"] == "orphaned_zpack"]
    assert len(found) == 1
    assert found[0]["object"] == orphan_hex
    assert found[0]["repairable"] is True
    assert found[0]["bytes"] == 77
    # Dry-run (default): lists, does not delete.
    dry = census.repair_orphaned_zpacks(found, apply=False)
    assert not dry["applied"]
    assert dry["freed_bytes"] == 77
    assert os.path.exists(orphan)
    # Apply: deletes the twin.
    applied = census.repair_orphaned_zpacks(found, apply=True)
    assert applied["applied"] and applied["freed_bytes"] == 77
    assert not os.path.exists(orphan)


def test_repair_skips_twin_whose_table_landed(tmp_path):
    """The audit→repair race: a table published between the audit and
    the repair re-legitimizes the twin — repair must re-verify NOW."""
    storage, _, _ = _populate(tmp_path)
    zdir = os.path.join(storage, "serve", "zpacks")
    os.makedirs(zdir, exist_ok=True)
    hx = "ab" * 32
    orphan = os.path.join(zdir, f"{hx}.zst")
    with open(orphan, "wb") as f:
        f.write(b"x")
    census = StorageCensus(storage)
    found = [f for f in census.audit()["findings"]
             if f["kind"] == "orphaned_zpack"]
    with open(os.path.join(storage, "serve", "packs",
                           f"{hx}.json"), "w") as f:
        f.write("[]")  # table lands after the audit
    out = census.repair_orphaned_zpacks(found, apply=True)
    assert out["skipped"] == 1 and not out["removed"]
    assert os.path.exists(orphan)


@pytest.mark.skipif(not zstdio.available(), reason="no zstd")
def test_audit_truncated_zpack(tmp_path):
    storage, doc, _ = _populate(tmp_path)
    pack_hex = doc["chunks"][0][2]
    zpath = os.path.join(storage, "serve", "zpacks",
                         f"{pack_hex}.zst")
    assert os.path.exists(zpath)
    size = os.path.getsize(zpath)
    with open(zpath, "rb+") as f:
        f.truncate(size - 1)
    out = StorageCensus(storage).audit()
    kinds = {f["kind"] for f in out["findings"]}
    assert "truncated_zpack" in kinds


# -- eviction dry-run ---------------------------------------------------------


def test_eviction_dry_run_lru_order_and_sum(tmp_path):
    storage, _, fps = _populate(tmp_path)
    oldest = cas_entry_path(os.path.join(storage, "chunks"), fps[1])
    past = os.path.getmtime(oldest) - 3600
    os.utime(oldest, (past, past))
    out = StorageCensus(storage).eviction_dry_run(3000)
    assert not out["refused"]
    assert out["current_bytes"] == 4580  # 4000 chunks + 580 blobs
    # LRU: the back-dated 3000-byte chunk goes first and suffices.
    assert out["would_evict"][0]["object"] == fps[1]
    assert out["freed_bytes"] >= 1500
    assert out["remaining_bytes"] == \
        out["current_bytes"] - out["freed_bytes"]
    assert out["remaining_bytes"] <= 3000


def test_eviction_dry_run_refuses_unseeded(tmp_path):
    storage, _, _ = _populate(tmp_path)
    out = StorageCensus(storage).eviction_dry_run(
        0, seed_state={"state": "seeding", "seeded_entries": 3})
    assert out["refused"]
    assert "seeding" in out["reason"]


def test_cas_seed_state_small_store_is_seeded(tmp_path):
    from makisu_tpu.storage.cas import CASStore
    store = CASStore(str(tmp_path / "cas"), max_entries=8)
    store.write_bytes("aa" * 32, b"x")
    state = store.seed_state()
    assert state["state"] == "seeded"
    assert state["seeded_entries"] == 1


# -- integrity scrub ----------------------------------------------------------


def test_scrub_clean_store(tmp_path):
    storage, _, _ = _populate(tmp_path)
    out = StorageCensus(storage).scrub(chunk_samples=10)
    assert out["chunks_checked"] == 2
    assert out["findings"] == []
    assert out["bytes_read"] >= 4000


def test_scrub_names_corrupt_chunk(tmp_path):
    storage, _, fps = _populate(tmp_path)
    victim = cas_entry_path(os.path.join(storage, "chunks"), fps[0])
    with open(victim, "rb+") as f:
        f.write(b"!")  # flip the first byte
    captured = []
    token = events.add_sink(captured.append)
    try:
        out = StorageCensus(storage).scrub(chunk_samples=10)
    finally:
        events.reset_sink(token)
    # The chunk finding is required; the zpack spot-check may ALSO
    # flag the same rot (the twin no longer matches the re-synthesized
    # raw range) — that second finding is correct, not double-counting.
    corrupt = [f for f in out["findings"]
               if f["kind"] == "corruption"
               and f["plane"] == "chunks"]
    assert len(corrupt) == 1
    assert corrupt[0]["expected"] == fps[0]
    assert corrupt[0]["actual"] != fps[0]
    assert corrupt[0]["path"] == victim
    # Findings ride the event bus as storage_finding events.
    kinds = [e for e in captured
             if e.get("type") == census_mod.EVENT_TYPE]
    assert kinds and kinds[0]["object"] == fps[0]


@pytest.mark.skipif(not zstdio.available(), reason="no zstd")
def test_scrub_names_corrupt_zpack_frame(tmp_path):
    storage, doc, _ = _populate(tmp_path)
    pack_hex = doc["chunks"][0][2]
    zpath = os.path.join(storage, "serve", "zpacks",
                         f"{pack_hex}.zst")
    with open(zpath, "rb+") as f:
        f.seek(os.path.getsize(zpath) // 2)
        f.write(b"\xff\xff\xff\xff")
    out = StorageCensus(storage).scrub(chunk_samples=0,
                                       pack_samples=4)
    corrupt = [f for f in out["findings"]
               if f["kind"] == "corruption" and f["plane"] == "packs"]
    assert corrupt
    assert corrupt[0]["object"] == pack_hex


# -- worker integration -------------------------------------------------------


def test_worker_healthz_and_storage_endpoint(tmp_path):
    from makisu_tpu.worker import WorkerClient, WorkerServer
    storage, _, fps = _populate(tmp_path)
    server = WorkerServer(str(tmp_path / "w.sock"))
    thread = server.serve_background()
    try:
        server._add_storage_dir(storage)
        client = WorkerClient(server.socket_path)
        health = client.healthz()
        section = health["storage"]
        assert section["planes"]["chunks"]["objects"] == 2
        assert section["total_bytes"] > 0
        assert section["lru_seed"]["state"] == "seeded"
        assert section["findings"]["total"] == 0
        # Break a reference (twin removed too — a recoverable miss
        # is demoted, not a finding); /storage re-walks and names it.
        os.unlink(cas_entry_path(os.path.join(storage, "chunks"),
                                 fps[0]))
        shutil.rmtree(os.path.join(storage, "serve", "zpacks"),
                      ignore_errors=True)
        report = client.storage(eviction_budget=0)
        (entry,) = report["storage"]
        kinds = {f["kind"] for f in entry["audit"]["findings"]}
        assert "dangling_chunk" in kinds
        assert not entry["eviction_dry_run"]["refused"]
        assert entry["eviction_dry_run"]["remaining_bytes"] == 0
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=5)


def test_fleet_doctor_flags_storage(tmp_path):
    from makisu_tpu.fleet import doctor as fleet_doctor
    health = {"fleet": {"workers": [{
        "id": "w0", "alive": True, "state": "alive",
        "storage": {
            "total_bytes": 10,
            "findings": {"total": 3,
                         "kinds": {"dangling_chunk": 3}},
            "lru_seed": {"state": "seeding",
                         "seeded_entries": 1}}}]},
        "self": {}}
    kinds = {f["kind"] for f in fleet_doctor.diagnose_fleet(health)}
    assert "storage_findings" in kinds
    assert "storage_unseeded" in kinds
    rendered = fleet_doctor.render_fleet_doctor(health, "sock")
    assert "STORAGE" in rendered


# -- CLI ----------------------------------------------------------------------


def test_cli_du_json_and_human(tmp_path, capsys):
    from makisu_tpu import cli
    storage, _, _ = _populate(tmp_path)
    assert cli.main(["du", "--storage", storage, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == census_mod.CENSUS_SCHEMA
    assert doc["planes"]["chunks"]["bytes"] == 4000
    assert cli.main(["du", "--storage", storage]) == 0
    human = capsys.readouterr().out
    assert "chunks" in human
    assert "unattributed" in human


def test_cli_doctor_storage_exit_codes(tmp_path, capsys):
    from makisu_tpu import cli
    storage, _, fps = _populate(tmp_path)
    assert cli.main(["doctor", "--storage", storage]) == 0
    out = capsys.readouterr().out
    assert "no findings" in out
    os.unlink(cas_entry_path(os.path.join(storage, "chunks"), fps[0]))
    shutil.rmtree(os.path.join(storage, "serve", "zpacks"),
                  ignore_errors=True)
    assert cli.main(["doctor", "--storage", storage]) == 1
    out = capsys.readouterr().out
    assert "dangling_chunk" in out
    assert fps[0][:12] in out


def test_cli_doctor_storage_repair(tmp_path, capsys):
    from makisu_tpu import cli
    storage, _, _ = _populate(tmp_path)
    zdir = os.path.join(storage, "serve", "zpacks")
    os.makedirs(zdir, exist_ok=True)
    orphan = os.path.join(zdir, "ab" * 32 + ".zst")
    with open(orphan, "wb") as f:
        f.write(b"x" * 9)
    # Findings exist → exit 1; dry-run leaves the twin in place.
    assert cli.main(["doctor", "--storage", storage]) == 1
    assert "would delete" in capsys.readouterr().out
    assert os.path.exists(orphan)
    assert cli.main(["doctor", "--storage", storage,
                     "--repair"]) == 1
    assert "deleted" in capsys.readouterr().out
    assert not os.path.exists(orphan)
    # Repaired store is clean again.
    assert cli.main(["doctor", "--storage", storage]) == 0


# -- chunks in segments (PR 49) ----------------------------------------------


def test_census_and_scrub_rows_over_segment_entries(tmp_path):
    """The chunk plane's rows are the layout's live entries, whichever
    form they have: a census counts a segment entry once with its
    record's size and stamp, and a scrub finding names the segment and
    the span the rotten bytes lie at (``where``), which is where a
    person has to look."""
    from makisu_tpu.storage import cas
    storage, _, fps = _populate(tmp_path)
    chunks_root = os.path.join(storage, "chunks")
    bare = cas.CASDir(chunks_root)
    assert sorted((n, s) for n, s, _ in bare.walk()) == sorted(
        zip(fps, (1000, 3000)))
    assert not [fn for _, _, files in os.walk(chunks_root)
                for fn in files if fn in fps]    # no file a chunk
    census = StorageCensus(storage)
    out = census.census()
    assert out["planes"]["chunks"]["objects"] == 2
    assert out["planes"]["chunks"]["bytes"] == 4000
    assert out["planes"]["chunks"]["age"]["1h"] == 2
    assert census.scrub(chunk_samples=10)["findings"] == []
    # Rot in place: one byte of the second chunk, in its segment.
    place = bare.where(fps[1])
    seg_path, span = place.rsplit("@", 1)
    offset, length = (int(x) for x in span.split("+"))
    assert length == 3000 and os.path.isfile(seg_path)
    with open(seg_path, "rb+") as f:
        f.seek(offset + 17)
        f.write(b"!")
    findings = [f for f in StorageCensus(storage).scrub(
        chunk_samples=10)["findings"]
        if f["kind"] == "corruption" and f["plane"] == "chunks"]
    assert len(findings) == 1
    assert findings[0]["expected"] == fps[1]
    assert findings[0]["path"] == place
    # A deleted entry leaves the rows; the audit sees it demoted.
    bare.delete(fps[0])
    assert [n for n, _, _ in cas.CASDir(chunks_root).walk()] == [fps[1]]
    assert StorageCensus(storage).census()["planes"]["chunks"][
        "objects"] == 1
