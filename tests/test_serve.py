"""Chunk-native distribution plane: recipe integrity, coalesced-range
planning, delta-pull byte identity and economics, corrupt-range
rejection, and the fleet peer plane riding ranged pack fetches."""

import json
import os
import time

import pytest

from makisu_tpu.builder import BuildPlan
from makisu_tpu.cache import CacheManager, MemoryStore
from makisu_tpu.cache.chunks import attach_chunk_dedup
from makisu_tpu.chunker import TPUHasher
from makisu_tpu.context import BuildContext
from makisu_tpu.docker.image import ImageName
from makisu_tpu.dockerfile import parse_file
from makisu_tpu.registry import RegistryClient, RegistryFixture
from makisu_tpu.serve import ServeServer, pull_image_delta
from makisu_tpu.serve import recipe as recipe_mod
from makisu_tpu.serve import server as serve_server_mod
from makisu_tpu.serve.client import ServeClient, plan_runs
from makisu_tpu.storage import ImageStore
from makisu_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _serve_enabled(monkeypatch):
    """Publishing on for every test here; the process-wide serve-store
    registry reset so one test's stores never answer for another's."""
    monkeypatch.setenv("MAKISU_TPU_SERVE", "1")
    serve_server_mod.reset_stores()
    yield
    serve_server_mod.reset_stores()


# -- recipe integrity ---------------------------------------------------------


def _recipe_doc():
    return {"schema": recipe_mod.RECIPE_SCHEMA,
            "layer": {"tar": "12" * 32, "gzip": "ab" * 32,
                      "size": 5, "gz": ""},
            "chunks": [["cd" * 32, 5, "ef" * 32, 0]]}


def test_recipe_seal_verify_roundtrip():
    doc = recipe_mod.seal(_recipe_doc(), key=b"")
    assert recipe_mod.verify(doc, key=b"")
    # Any body tamper breaks the self-digest.
    tampered = dict(doc)
    tampered["chunks"] = [["cd" * 32, 6, "ef" * 32, 0]]
    assert not recipe_mod.verify(tampered, key=b"")


def test_recipe_malformed_documents_refused():
    """A sealed-but-structurally-broken document must be a MISS, not
    a KeyError inside a pull or peer fetch."""
    for mangle in (
            lambda d: d.pop("layer"),
            lambda d: d.pop("chunks"),
            lambda d: d["layer"].pop("gzip"),
            lambda d: d["layer"].__setitem__("size", "big"),
            lambda d: d["chunks"].append(["cd" * 32, 5, "ef" * 32]),
            lambda d: d["chunks"].append(["nothex", 5, "ef" * 32, 0]),
            lambda d: d["chunks"].append(["cd" * 32, 0, "ef" * 32, 0]),
            lambda d: d.__setitem__("packs", "notadict"),
            lambda d: d.__setitem__("packs", {"ef" * 32: 0}),
            lambda d: d.__setitem__("packs", {"nothex": 7}),
    ):
        doc = _recipe_doc()
        mangle(doc)
        recipe_mod.seal(doc, key=b"")  # valid digest over the lie
        assert not recipe_mod.verify(doc, key=b""), doc


def test_recipe_signature_required_when_keyed():
    signed = recipe_mod.seal(_recipe_doc(), key=b"k1")
    assert recipe_mod.verify(signed, key=b"k1")
    # Wrong key and unsigned both refuse under a keyed verifier.
    assert not recipe_mod.verify(signed, key=b"k2")
    unsigned = recipe_mod.seal(_recipe_doc(), key=b"")
    assert not recipe_mod.verify(unsigned, key=b"k1")
    # A keyless client accepts both (nothing to verify against).
    assert recipe_mod.verify(signed, key=b"")


def test_published_recipe_carries_true_pack_sizes(tmp_path):
    """A later layer referencing a sliver of a shared pack must still
    see the pack's TRUE size in its recipe's ``packs`` map — the
    client's runs-vs-whole decision uses the same denominator as the
    registry path, not the extent one recipe happens to reference."""
    import hashlib
    from makisu_tpu.cache.chunks import ChunkStore
    from makisu_tpu.docker.image import (
        MEDIA_TYPE_LAYER, Descriptor, Digest, DigestPair)
    store = ChunkStore(str(tmp_path / "chunks"))
    rs = recipe_mod.RecipeStore(str(tmp_path / "serve"),
                                str(tmp_path / "chunks"))
    c1, c2 = b"a" * 1000, b"b" * 3000
    fps = [hashlib.sha256(c).hexdigest() for c in (c1, c2)]
    for fp, data in zip(fps, (c1, c2)):
        store.put(fp, data)

    def pair_for(seed):
        return DigestPair(
            tar_digest=Digest.from_hex(f"{seed:02x}" * 32),
            gzip_descriptor=Descriptor(
                MEDIA_TYPE_LAYER, 10,
                Digest.from_hex(f"{seed + 1:02x}" * 32)))

    doc1 = rs.publish(pair_for(0x10),
                      [(0, 1000, fps[0]), (1000, 3000, fps[1])],
                      None, store)
    assert doc1 is not None and recipe_mod.verify(doc1, key=b"")
    (pack_hex,) = {row[2] for row in doc1["chunks"]}
    assert doc1["packs"] == {pack_hex: 4000}
    # Layer 2 reuses only c1: its rows reference 1000 bytes of the
    # pack, but the size map must carry the full 4000.
    doc2 = rs.publish(pair_for(0x20), [(0, 1000, fps[0])], None, store)
    assert doc2 is not None and recipe_mod.verify(doc2, key=b"")
    assert doc2["chunks"][0][2] == pack_hex
    assert doc2["packs"] == {pack_hex: 4000}


def test_standalone_serve_server_is_read_only(tmp_path, monkeypatch):
    """ServeServer must not flip the process-global publishing switch:
    it never indexes layers, and the flip would leak recipe-publish
    cost into builds an embedder (bench) runs later in the process."""
    monkeypatch.delenv("MAKISU_TPU_SERVE", raising=False)
    monkeypatch.setattr(serve_server_mod, "_publishing", False)
    server = ServeServer(str(tmp_path / "s.sock"), str(tmp_path))
    try:
        assert not serve_server_mod.publish_enabled()
    finally:
        server.server_close()


def test_stream_triples_offsets_are_running_sum():
    rows = [["aa" * 32, 10, "p" * 64, 0], ["bb" * 32, 7, "p" * 64, 10]]
    assert recipe_mod.stream_triples(rows) == [
        (0, 10, "aa" * 32), (10, 7, "bb" * 32)]


# -- range planning -----------------------------------------------------------


def _rows(pack, spans):
    """[(fp, off, length)] → recipe rows in one pack."""
    return [[fp, length, pack, off] for fp, off, length in spans]


def test_plan_runs_coalesces_adjacent_spans():
    pack = "ab" * 32
    rows = _rows(pack, [("f1", 0, 100), ("f2", 100, 50),
                        ("f3", 5_000_000, 80)])
    run_jobs, whole_jobs = plan_runs(
        rows, {"f1", "f2", "f3"},
        pack_sizes={pack: 50_000_000})
    assert not whole_jobs
    assert len(run_jobs) == 1
    _, runs = run_jobs[0]
    # f1+f2 adjacent → one run; f3 is megabytes away → its own run.
    # 3 missing chunks cost 2 requests, not 3 (the vs-per-chunk
    # economics the plane exists for).
    assert len(runs) == 2
    assert [(s[0], s[1]) for s in runs[0]] == [(0, 100), (100, 50)]
    assert runs[1][0][0] == 5_000_000


def test_plan_runs_gap_tolerance_merges_nearby_spans():
    pack = "cd" * 32
    rows = _rows(pack, [("f1", 0, 100), ("f2", 200, 100)])
    # A 100-byte gap (held chunk between) still coalesces: one request
    # over-fetches 100 bytes instead of paying a second round trip.
    run_jobs, _ = plan_runs(rows, {"f1", "f2"},
                            pack_sizes={pack: 10_000_000})
    (_, runs), = run_jobs
    assert len(runs) == 1
    start = runs[0][0][0]
    end = runs[0][-1][0] + runs[0][-1][1]
    assert (start, end) == (0, 300)


def test_plan_runs_mostly_needed_pack_fetches_whole():
    pack = "ef" * 32
    rows = _rows(pack, [("f1", 0, 600), ("f2", 600, 300)])
    run_jobs, whole_jobs = plan_runs(rows, {"f1", "f2"},
                                     pack_sizes={pack: 1000})
    assert whole_jobs == [pack]
    assert not run_jobs


def test_fetch_missing_survives_dual_coordinate_recipe():
    """A sealed, well-formed recipe can still LIE: one fingerprint
    mapped to two different pack coordinates. First coordinate wins
    for both the planner and the carve table — one fetch, no KeyError
    out of the engine (the blob route is the degradation for every
    bad-recipe shape, never a traceback)."""
    import hashlib

    from makisu_tpu.serve.client import fetch_missing
    data = b"Z" * 1000
    fp = hashlib.sha256(data).hexdigest()
    rows = [[fp, 1000, "a" * 64, 0], [fp, 1000, "b" * 64, 0]]
    fetched_packs = []

    def fetch_range(pack_hex, start, end, limit=None):
        fetched_packs.append(pack_hex)
        return "partial", data[start:end]

    stored = {}
    got, _ = fetch_missing(fetch_range, rows, {fp},
                           lambda f, b: stored.__setitem__(f, b))
    assert got == {fp}
    assert stored[fp] == data
    assert fetched_packs == ["a" * 64]


def test_parse_range_semantics():
    parse = serve_server_mod.parse_range
    assert parse("bytes=0-99", 1000) == (0, 100)
    assert parse("bytes=900-", 1000) == (900, 1000)
    assert parse("bytes=900-5000", 1000) == (900, 1000)  # clamped
    assert parse("bytes=1000-1099", 1000) == "unsatisfiable"
    # No/unparseable/multi/inverted ranges degrade to a full answer
    # (an inverted range must NOT produce a negative Content-Length).
    assert parse(None, 1000) is None
    assert parse("bytes=a-b", 1000) is None
    assert parse("bytes=0-1,5-9", 1000) is None
    assert parse("bytes=5-3", 1000) is None


# -- end-to-end delta pulls ---------------------------------------------------


def _payload(seed, size=1_500_000):
    import numpy as np
    return np.random.default_rng(seed).integers(
        0, 256, size=size, dtype=np.uint8).tobytes()


def _text_payload(seed, size=1_500_000):
    """Compressible-but-chunkable content (log-like lines with random
    ids): the shape where the seekable-zstd wire actually wins — pure
    random makes zstd a net loss and the client rightly keeps the raw
    wire (it prices both from the frame index)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 2 ** 48, size=size // 40 + 1)
    text = b"".join(b"req %012x served from cache tier A\n" % int(i)
                    for i in ids)
    return text[:size]


class _Plane:
    """One builder storage + registry fixture + serve socket: the
    publishing side of the distribution plane, build-by-build."""

    def __init__(self, tmp_path):
        self.tmp = tmp_path
        self.kv = MemoryStore()
        self.fixture = RegistryFixture()
        self.storage = str(tmp_path / "builder-storage")
        self.server = None

    def build_and_push(self, tag, payload):
        ctx_dir = self.tmp / f"ctx-{tag}"
        ctx_dir.mkdir(exist_ok=True)
        (ctx_dir / "blob.bin").write_bytes(payload)
        root = self.tmp / f"root-{tag}"
        root.mkdir(exist_ok=True)
        store = ImageStore(self.storage)
        client = RegistryClient(store, "registry.test", "t/app",
                                transport=self.fixture)
        ctx = BuildContext(str(root), str(ctx_dir), store,
                           hasher=TPUHasher(), sync_wait=0.0)
        mgr = CacheManager(self.kv, store, registry_client=client)
        attach_chunk_dedup(mgr, os.path.join(self.storage, "chunks"))
        stages = parse_file("FROM scratch\nCOPY blob.bin /blob.bin\n")
        name = ImageName("registry.test", "t/app", tag)
        plan = BuildPlan(ctx, name, [], mgr, stages,
                         allow_modify_fs=False, force_commit=True)
        manifest = plan.execute()
        mgr.wait_for_push()
        push_client = RegistryClient(store, "registry.test", "t/app",
                                     transport=self.fixture)
        push_client.materialize_blob = mgr.materialize
        mgr.materialize_pending()
        push_client.push(name)
        return manifest

    def serve(self):
        sock = str(self.tmp / "serve.sock")
        self.server = ServeServer(sock, self.storage)
        self.server.serve_background()
        return sock

    def puller(self, name="client"):
        store = ImageStore(str(self.tmp / f"{name}-storage"))
        reg = RegistryClient(store, "registry.test", "t/app",
                             transport=self.fixture)
        return store, reg


def test_delta_pull_one_edit_byte_identity(tmp_path):
    """The acceptance scenario: pull v1 (seeds the client chunk CAS),
    1-edit rebuild, pull v2 — the v2 pull must fetch < 10% of
    full-image bytes and every reconstituted layer must be
    byte-identical to a cold full pull."""
    plane = _Plane(tmp_path)
    v1 = _payload(7)
    v2 = v1[:9_000] + b"EDIT-ONE-FILE" + v1[9_000:]
    plane.build_and_push("v1", v1)
    sock = plane.serve()

    cstore, creg = plane.puller()
    n1 = ImageName("registry.test", "t/app", "v1")
    _, rep1 = pull_image_delta(creg, cstore, n1, sock)
    # Cold delta pull: everything arrives, but over the pack wire.
    assert rep1["delta_layers"] >= 1, rep1
    assert rep1["fallback_layers"] == 0, rep1

    plane.build_and_push("v2", v2)
    n2 = ImageName("registry.test", "t/app", "v2")
    _, rep2 = pull_image_delta(creg, cstore, n2, sock)
    assert rep2["delta_layers"] >= 1, rep2
    assert rep2["fetched_fraction"] < 0.10, rep2
    # Coalescing: the novel region is contiguous, so the whole delta
    # should cost a handful of range requests, not one per chunk.
    delta_rows = [r for r in rep2["layers"] if r["route"] == "delta"]
    assert sum(r["requests"] for r in delta_rows) < \
        sum(r["chunks_missing"] for r in delta_rows) + 2

    # Byte identity vs a cold full pull.
    ostore, oreg = plane.puller("oracle")
    om = oreg.pull(n2)
    for desc in om.layers:
        hx = desc.digest.hex()
        with ostore.layers.open(hx) as fa, cstore.layers.open(hx) as fb:
            assert fa.read() == fb.read(), f"layer {hx} differs"


def test_delta_pull_unpublished_layer_falls_back_to_blob(tmp_path):
    """No recipe (publishing disabled during the build): pull --delta
    must degrade to the registry blob route, still correct."""
    plane = _Plane(tmp_path)
    os.environ["MAKISU_TPU_SERVE"] = "0"
    try:
        plane.build_and_push("v1", _payload(11))
    finally:
        os.environ["MAKISU_TPU_SERVE"] = "1"
    sock = plane.serve()
    cstore, creg = plane.puller()
    n1 = ImageName("registry.test", "t/app", "v1")
    _, rep = pull_image_delta(creg, cstore, n1, sock)
    assert rep["delta_layers"] == 0, rep
    assert rep["fallback_layers"] >= 1, rep
    for desc in creg.pull_manifest("v1").layers:
        assert cstore.layers.exists(desc.digest.hex())


def test_corrupt_pack_range_rejected(tmp_path):
    """A serving CAS corrupted on disk: carved chunks fail their
    sha256 and are never stored, the delta route reports failure, and
    the pull falls back to the registry blob route — corrupt serve
    bytes can waste bandwidth, never install."""
    plane = _Plane(tmp_path)
    plane.build_and_push("v1", _payload(13))
    sock = plane.serve()

    # Flip a byte in every served chunk ≥ 4KiB (the pack spans will
    # carve garbage) AND in every seekable frame file (written at
    # publish time from the then-healthy CAS, it would otherwise still
    # serve the original bytes — correct, but not this test's
    # scenario: a serving store corrupted across the board).
    from conftest import cas_entry_path
    from makisu_tpu.storage.cas import CASDir
    chunk_dir = os.path.join(plane.storage, "chunks")
    flipped = 0
    for fname, size, _ in list(CASDir(chunk_dir).walk()):
        if not recipe_mod.is_hex_digest(fname) or size < 4096:
            continue
        with open(cas_entry_path(chunk_dir, fname), "r+b") as f:
            f.seek(100)
            byte = f.read(1)
            f.seek(100)
            f.write(bytes([byte[0] ^ 0xFF]))
        flipped += 1
    assert flipped, "expected chunk files to corrupt"
    zpack_dir = os.path.join(plane.storage, "serve", "zpacks")
    for fname in os.listdir(zpack_dir):
        path = os.path.join(zpack_dir, fname)
        with open(path, "r+b") as f:
            f.seek(50)
            byte = f.read(1)
            f.seek(50)
            f.write(bytes([byte[0] ^ 0xFF]))

    cstore, creg = plane.puller()
    n1 = ImageName("registry.test", "t/app", "v1")
    _, rep = pull_image_delta(creg, cstore, n1, sock)
    assert rep["delta_layers"] == 0, rep
    assert rep["fallback_layers"] >= 1, rep
    # Nothing corrupt installed: blobs match the registry's bytes.
    manifest = creg.pull_manifest("v1")
    for desc in manifest.layers:
        hx = desc.digest.hex()
        with cstore.layers.open(hx) as f:
            data = f.read()
        import hashlib
        assert hashlib.sha256(data).hexdigest() == hx


def test_lying_recipe_never_installs(tmp_path):
    """A recipe whose chunk table reconstitutes to the wrong bytes
    (tampered post-seal) fails verification client-side; a re-sealed
    lie passes verification but the reconstituted digests refuse."""
    plane = _Plane(tmp_path)
    manifest = plane.build_and_push("v1", _payload(17))
    hex_digest = manifest.layers[0].digest.hex()
    store = serve_server_mod.store_for(plane.storage)
    doc = store.recipe(hex_digest)
    assert doc is not None
    # Drop a row and re-seal: valid signature, wrong content.
    doc["chunks"] = doc["chunks"][:-1]
    recipe_mod.seal(doc)
    path = os.path.join(plane.storage, "serve", "recipes",
                        f"{hex_digest}.json")
    with open(path, "w") as f:
        json.dump(doc, f)
    sock = plane.serve()
    cstore, creg = plane.puller()
    n1 = ImageName("registry.test", "t/app", "v1")
    _, rep = pull_image_delta(creg, cstore, n1, sock)
    # Size mismatch (or digest mismatch on reconstitute) → blob route.
    assert rep["delta_layers"] == 0, rep
    for desc in creg.pull_manifest("v1").layers:
        assert cstore.layers.exists(desc.digest.hex())


def test_serve_pack_endpoint_range_semantics(tmp_path):
    """Wire-level: 206 + Content-Range for a partial span, 200 for no
    Range, 416 past the end, 404 for an unknown pack."""
    plane = _Plane(tmp_path)
    manifest = plane.build_and_push("v1", _payload(19))
    sock = plane.serve()
    store = serve_server_mod.store_for(plane.storage)
    doc = store.recipe(manifest.layers[0].digest.hex())
    pack_hex = doc["chunks"][0][2]
    size = store.pack_size(pack_hex)
    assert size > 0
    client = ServeClient(sock)
    kind, body = client.pack_range(pack_hex, 0, min(1000, size))
    assert kind == "partial" and len(body) == min(1000, size)
    status, _, body = client._get(f"/packs/{pack_hex}")
    assert status == 200 and len(body) == size
    status, _, _ = client._get(
        f"/packs/{pack_hex}", headers={"Range": f"bytes={size}-"})
    assert status == 416
    status, _, _ = client._get(f"/packs/{'0' * 64}")
    assert status == 404
    status, _, _ = client._get("/packs/not-a-digest")
    assert status == 400


# -- seekable-zstd packs ------------------------------------------------------


def _zstd_required():
    from makisu_tpu.utils import zstdio
    if not zstdio.available():
        pytest.skip("libzstd not available on this host")
    return zstdio


def test_seekable_frame_index_roundtrip(tmp_path):
    """Publish writes the compressed twin + frame index: frames are
    whole-chunk groups, decompress independently, and concatenate back
    to the exact raw pack bytes; a FRESH store (new process) re-loads
    the dict-form pack table with its frames."""
    import hashlib
    zstdio = _zstd_required()
    from makisu_tpu.cache.chunks import ChunkStore
    from makisu_tpu.docker.image import (
        MEDIA_TYPE_LAYER, Descriptor, Digest, DigestPair)
    store = ChunkStore(str(tmp_path / "chunks"))
    rs = recipe_mod.RecipeStore(str(tmp_path / "serve"),
                                str(tmp_path / "chunks"))
    rng_chunks = [os.urandom(50_000) for _ in range(10)]
    triples, off = [], 0
    for data in rng_chunks:
        fp = hashlib.sha256(data).hexdigest()
        store.put(fp, data)
        triples.append((off, len(data), fp))
        off += len(data)
    pair = DigestPair(
        tar_digest=Digest.from_hex("12" * 32),
        gzip_descriptor=Descriptor(MEDIA_TYPE_LAYER, off,
                                   Digest.from_hex("34" * 32)))
    doc = rs.publish(pair, triples, None, store)
    assert doc is not None and recipe_mod.verify(doc, key=b"")
    (pack_hex,) = {row[2] for row in doc["chunks"]}
    frames = doc["zpacks"][pack_hex]
    assert frames and recipe_mod._frame_rows_valid(frames)
    raw = b"".join(rng_chunks)
    # Frames tile the raw pack exactly and decompress independently.
    assert frames[0][0] == 0
    assert sum(r[1] for r in frames) == len(raw)
    zpath = os.path.join(str(tmp_path / "serve"), "zpacks",
                         f"{pack_hex}.zst")
    zblob = open(zpath, "rb").read()
    assert len(zblob) == frames[-1][2] + frames[-1][3]
    rebuilt = b"".join(
        zstdio.decompress(zblob[z_off:z_off + z_len], raw_len)
        for _, raw_len, z_off, z_len in
        ((r[0], r[1], r[2], r[3]) for r in frames))
    assert rebuilt == raw
    # A fresh store (another process) parses the dict-form table.
    rs2 = recipe_mod.RecipeStore(str(tmp_path / "serve"),
                                 str(tmp_path / "chunks"))
    assert rs2.pack_frames(pack_hex) == [
        [int(v) for v in row] for row in frames]
    assert rs2.zpack_size(pack_hex) == len(zblob)


def test_malformed_frame_index_demotes_to_raw_serving(tmp_path):
    """A pack table whose frame rows are garbage (non-int, wrong
    shape) must keep serving its intact member table raw — the frames
    are an optimization, never allowed to 404 the pack."""
    os.makedirs(tmp_path / "serve" / "packs", exist_ok=True)
    pack_hex = "ab" * 32
    with open(tmp_path / "serve" / "packs" / f"{pack_hex}.json",
              "w") as f:
        json.dump({"members": [["cd" * 32, 100]],
                   "frames": [["x", 1, 2, 3]]}, f)
    rs = recipe_mod.RecipeStore(str(tmp_path / "serve"),
                                str(tmp_path / "chunks"))
    assert rs.pack_members(pack_hex) == [("cd" * 32, 100)]
    assert rs.pack_frames(pack_hex) is None
    assert rs.zpack_size(pack_hex) == 0


def test_plan_frame_runs_maps_spans_to_frames():
    from makisu_tpu.cache.chunks import plan_frame_runs
    # 4 frames of 100 raw bytes; compressed 40 each at z offsets 0..160.
    frames = [[0, 100, 0, 40], [100, 100, 40, 40],
              [200, 100, 80, 40], [300, 100, 120, 40]]
    # A span inside frame 0 and one crossing frames 2→3: frame 1 is
    # not needed, so its 40 compressed bytes split the plan into two
    # runs at gap=0 — and a crossing span names BOTH its frames.
    runs = plan_frame_runs(frames, [(20, 10, "f1"), (290, 20, "f2")],
                           gap=0)
    assert runs == [[[0, 100, 0, 40]],
                    [[200, 100, 80, 40], [300, 100, 120, 40]]]
    # With a generous gap the two runs coalesce into one request:
    # frame 1's bytes are over-fetched inside the range but stay out
    # of the run's rows (never decompressed — only needed frames are).
    runs = plan_frame_runs(frames, [(20, 10, "f1"), (290, 20, "f2")],
                           gap=1000)
    assert len(runs) == 1 and len(runs[0]) == 3
    assert [r[0] for r in runs[0]] == [0, 200, 300]
    # Needed frames that are z-adjacent always share a run.
    runs = plan_frame_runs(frames, [(120, 10, "f1"), (290, 20, "f2")],
                           gap=0)
    assert len(runs) == 1 and len(runs[0]) == 3


def test_serve_zpack_endpoint_ranged_mid_pack_frame(tmp_path):
    """Wire-level /zpacks: a mid-pack frame fetched by compressed
    Range decompresses to exactly that frame's raw bytes; 416 past the
    end; 404 for frame-less hexes."""
    zstdio = _zstd_required()
    plane = _Plane(tmp_path)
    manifest = plane.build_and_push("v1", _payload(29))
    sock = plane.serve()
    store = serve_server_mod.store_for(plane.storage)
    doc = store.recipe(manifest.layers[0].digest.hex())
    pack_hex = doc["chunks"][0][2]
    frames = store.pack_frames(pack_hex)
    assert frames and len(frames) >= 3, "expected a multi-frame pack"
    mid = frames[len(frames) // 2]
    raw_off, raw_len, z_off, z_len = mid
    client = ServeClient(sock)
    kind, body = client.zpack_range(pack_hex, z_off, z_off + z_len)
    assert kind == "partial" and len(body) == z_len
    rawbuf = zstdio.decompress(body, raw_len)
    # The decompressed frame equals the raw pack's same span.
    kind, rawspan = client.pack_range(pack_hex, raw_off,
                                      raw_off + raw_len)
    assert kind == "partial" and rawbuf == rawspan
    zsize = store.zpack_size(pack_hex)
    status, _, _ = client._get(
        f"/zpacks/{pack_hex}", headers={"Range": f"bytes={zsize}-"})
    assert status == 416
    status, _, _ = client._get(f"/zpacks/{'0' * 64}")
    assert status == 404
    status, _, _ = client._get("/zpacks/not-a-digest")
    assert status == 400


def test_delta_pull_rides_compressed_wire(tmp_path):
    """The seekable acceptance: a 1-edit delta pull moves FEWER wire
    bytes than the raw-pack plan would have (bytes_fetched <=
    bytes_raw_wire, with zstd requests actually on the wire), digests
    byte-identical."""
    _zstd_required()
    g = metrics.global_registry()
    before_z = (g.counter_total(metrics.SERVE_PACK_REQUESTS,
                                kind="zrange")
                + g.counter_total(metrics.SERVE_PACK_REQUESTS,
                                  kind="zfull"))
    plane = _Plane(tmp_path)
    v1 = _text_payload(31)
    v2 = v1[:9_000] + b"EDIT" + v1[9_000:]
    plane.build_and_push("v1", v1)
    sock = plane.serve()
    cstore, creg = plane.puller()
    pull_image_delta(creg, cstore,
                     ImageName("registry.test", "t/app", "v1"), sock)
    plane.build_and_push("v2", v2)
    n2 = ImageName("registry.test", "t/app", "v2")
    _, rep = pull_image_delta(creg, cstore, n2, sock)
    assert rep["delta_layers"] >= 1, rep
    assert rep["bytes_fetched"] < rep["bytes_raw_wire"], rep
    z_requests = (g.counter_total(metrics.SERVE_PACK_REQUESTS,
                                  kind="zrange")
                  + g.counter_total(metrics.SERVE_PACK_REQUESTS,
                                    kind="zfull")) - before_z
    assert z_requests >= 1, "delta never touched the compressed wire"
    # Byte identity vs a cold full pull.
    ostore, oreg = plane.puller("oracle")
    om = oreg.pull(n2)
    for desc in om.layers:
        hx = desc.digest.hex()
        with ostore.layers.open(hx) as fa, cstore.layers.open(hx) as fb:
            assert fa.read() == fb.read()


def test_old_client_keeps_raw_pack_wire(tmp_path, monkeypatch):
    """Capability negotiation, client side: a puller without zstd (old
    binary, no libzstd) must ride the raw /packs wire end to end —
    same bytes installed, zero /zpacks requests."""
    from makisu_tpu.utils import zstdio
    plane = _Plane(tmp_path)
    plane.build_and_push("v1", _payload(37))
    sock = plane.serve()
    g = metrics.global_registry()
    before_z = g.counter_total(metrics.SERVE_PACK_REQUESTS,
                               kind="zrange")
    monkeypatch.setattr(zstdio, "available", lambda: False)
    cstore, creg = plane.puller()
    n1 = ImageName("registry.test", "t/app", "v1")
    _, rep = pull_image_delta(creg, cstore, n1, sock)
    assert rep["delta_layers"] >= 1, rep
    assert rep["bytes_fetched"] == rep["bytes_raw_wire"], rep
    assert g.counter_total(metrics.SERVE_PACK_REQUESTS,
                           kind="zrange") == before_z
    for desc in creg.pull_manifest("v1").layers:
        assert cstore.layers.exists(desc.digest.hex())


def test_lying_frame_never_installs(tmp_path):
    """A corrupted/lying frame file: decompression fails or carved
    chunks fail sha256 — either way nothing corrupt installs; the raw
    pack wire (or blob route) produces the correct bytes."""
    import hashlib
    _zstd_required()
    plane = _Plane(tmp_path)
    plane.build_and_push("v1", _text_payload(41))
    # Corrupt every seekable frame file; leave the chunk CAS healthy.
    zpack_dir = os.path.join(plane.storage, "serve", "zpacks")
    for fname in os.listdir(zpack_dir):
        path = os.path.join(zpack_dir, fname)
        blob = bytearray(open(path, "rb").read())
        for i in range(0, len(blob), 97):
            blob[i] ^= 0xA5
        open(path, "wb").write(bytes(blob))
    sock = plane.serve()
    cstore, creg = plane.puller()
    n1 = ImageName("registry.test", "t/app", "v1")
    _, rep = pull_image_delta(creg, cstore, n1, sock)
    # The pull still lands (raw wire fallback) and installs only
    # registry-digest-verified bytes.
    for desc in creg.pull_manifest("v1").layers:
        hx = desc.digest.hex()
        with cstore.layers.open(hx) as f:
            assert hashlib.sha256(f.read()).hexdigest() == hx


# -- fleet peer plane on the pack wire ---------------------------------------


def test_fleet_peer_exchange_is_pack_granular(tmp_path, monkeypatch):
    """Drain the builder worker and rebuild on its sibling: the
    relocated build's chunks must arrive as ranged pack fetches
    (SERVE_PEER_PACK_REQUESTS, /packs on the serving side), NOT as
    per-chunk GETs — and fewer requests than chunks must hit the
    wire. The session-snapshot plane is disabled here: drain/prewarm
    shard staging rides the per-chunk wire by design (shards are not
    pack members), and this test pins the LAYER exchange in
    isolation — the snapshot wire is covered by
    tests/test_session_snapshot.py and loadgen --prewarm-smoke."""
    monkeypatch.setenv("MAKISU_TPU_SESSION_SNAPSHOT", "0")
    from tests.test_fleet import (
        _Fleet,
        _build_argv,
        _digests,
        _make_ctx,
    )
    from makisu_tpu.fleet import peers as fleet_peers
    fleet_peers.reset()
    g = metrics.global_registry()
    before = {
        "pack_req": g.counter_total(metrics.SERVE_PEER_PACK_REQUESTS),
        "chunk_serves": g.counter_total(
            "makisu_fleet_chunk_serves_total", result="hit"),
        "pack_range": g.counter_total(metrics.SERVE_PACK_REQUESTS,
                                      kind="range"),
        "pack_full": g.counter_total(metrics.SERVE_PACK_REQUESTS,
                                     kind="full"),
        "pack_zrange": g.counter_total(metrics.SERVE_PACK_REQUESTS,
                                       kind="zrange"),
        "pack_zfull": g.counter_total(metrics.SERVE_PACK_REQUESTS,
                                      kind="zfull"),
    }
    fleet = _Fleet(tmp_path, n=2)
    try:
        ctx = _make_ctx(tmp_path, "packpeer-ctx", files=6)
        argv = _build_argv(tmp_path, ctx, fleet.kv_addr)
        assert fleet.client.build(argv, tenant="t") == 0
        first = dict(fleet.client.last_build)
        holder = first["worker"]
        fleet.drain(holder)
        deadline = time.monotonic() + 10
        while True:
            workers = {w["id"]: w for w in
                       fleet.client.healthz()["fleet"]["workers"]}
            if workers[holder]["state"] == "draining":
                break
            assert time.monotonic() < deadline
            time.sleep(0.05)
        assert fleet.client.build(argv, tenant="t") == 0
        second = dict(fleet.client.last_build)
        assert second["worker"] != holder

        pack_requests = g.counter_total(
            metrics.SERVE_PEER_PACK_REQUESTS) - before["pack_req"]
        served = (g.counter_total(metrics.SERVE_PACK_REQUESTS,
                                  kind="range")
                  + g.counter_total(metrics.SERVE_PACK_REQUESTS,
                                    kind="full")
                  + g.counter_total(metrics.SERVE_PACK_REQUESTS,
                                    kind="zrange")
                  + g.counter_total(metrics.SERVE_PACK_REQUESTS,
                                    kind="zfull")
                  - before["pack_range"] - before["pack_full"]
                  - before["pack_zrange"] - before["pack_zfull"])
        per_chunk = g.counter_total(
            "makisu_fleet_chunk_serves_total",
            result="hit") - before["chunk_serves"]
        assert pack_requests >= 1, "peer exchange never used packs"
        assert served >= 1, "no worker served a /packs request"
        assert per_chunk == 0, \
            "per-chunk GETs used despite a published recipe"
        # Digest identity across the relocation.
        tag = f"fleet/{ctx.name}:1"
        d1 = _digests(fleet.specs[holder].storage, tag)
        d2 = _digests(fleet.specs[second["worker"]].storage, tag)
        assert d1 == d2
        # The scheduler surfaces each worker's serve digest — via its
        # periodic /healthz poll, so give the cached snapshot time to
        # catch up with the holder's publish (same discipline as the
        # draining-state wait above).
        deadline = time.monotonic() + 10
        while True:
            health = fleet.client.healthz()
            rows = {w["id"]: w for w in health["fleet"]["workers"]}
            if rows[holder]["serve"].get("recipes", 0) >= 1:
                break
            assert time.monotonic() < deadline, rows
            time.sleep(0.05)
    finally:
        fleet.close()
        fleet_peers.reset()


def test_fleet_peer_falls_back_per_chunk_without_recipe(tmp_path):
    """Old-worker compatibility: publishing off (no recipes anywhere)
    must leave the per-chunk GET route working."""
    from tests.test_fleet import _Fleet, _build_argv, _make_ctx
    from makisu_tpu.fleet import peers as fleet_peers
    os.environ["MAKISU_TPU_SERVE"] = "0"
    fleet_peers.reset()
    g = metrics.global_registry()
    before_chunk = g.counter_total("makisu_fleet_chunk_serves_total",
                                   result="hit")
    before_pack = g.counter_total(metrics.SERVE_PEER_PACK_REQUESTS)
    fleet = _Fleet(tmp_path, n=2)
    try:
        ctx = _make_ctx(tmp_path, "oldpeer-ctx")
        argv = _build_argv(tmp_path, ctx, fleet.kv_addr)
        assert fleet.client.build(argv, tenant="t") == 0
        holder = dict(fleet.client.last_build)["worker"]
        fleet.drain(holder)
        deadline = time.monotonic() + 10
        while True:
            workers = {w["id"]: w for w in
                       fleet.client.healthz()["fleet"]["workers"]}
            if workers[holder]["state"] == "draining":
                break
            assert time.monotonic() < deadline
            time.sleep(0.05)
        assert fleet.client.build(argv, tenant="t") == 0
        assert g.counter_total("makisu_fleet_chunk_serves_total",
                               result="hit") > before_chunk, \
            "per-chunk fallback never served"
        assert g.counter_total(
            metrics.SERVE_PEER_PACK_REQUESTS) == before_pack
    finally:
        fleet.close()
        fleet_peers.reset()
        os.environ["MAKISU_TPU_SERVE"] = "1"


def test_worker_serves_recipes_and_packs_for_own_roots_only(tmp_path):
    """Per-server honesty scoping carried over from /chunks: a worker
    answers /recipes and /packs only for storages its own builds
    used."""
    from makisu_tpu.worker import WorkerServer
    plane = _Plane(tmp_path)
    manifest = plane.build_and_push("v1", _payload(23))
    hex_digest = manifest.layers[0].digest.hex()

    sock_a = str(tmp_path / "wa.sock")
    server_a = WorkerServer(sock_a)
    thread_a = server_a.serve_background()
    sock_b = str(tmp_path / "wb.sock")
    server_b = WorkerServer(sock_b)
    thread_b = server_b.serve_background()
    try:
        server_a.add_served_chunk_root(plane.storage)
        client_a = ServeClient(sock_a)
        doc = client_a.recipe(hex_digest)
        assert doc is not None
        pack_hex = doc["chunks"][0][2]
        assert client_a.pack_range(pack_hex, 0, 100) is not None
        # Worker B never built against this storage: 404s.
        client_b = ServeClient(sock_b)
        assert client_b.recipe(hex_digest) is None
        assert client_b.pack_range(pack_hex, 0, 100) is None
    finally:
        for server, thread in ((server_a, thread_a),
                               (server_b, thread_b)):
            server.shutdown()
            server.server_close()
            thread.join(timeout=5)


# -- the publication fed from a pass (PR 48) ---------------------------------

def _fed_layer(tmp_path, storage, pieces, name):
    """A gzip blob of ``pieces`` committed into ``storage``'s layer
    store: (blob path, chunk list, digest pair, LayerCommit)."""
    import gzip
    import hashlib
    from conftest import committed_layer
    chunks, pos = [], 0
    for piece in pieces:
        chunks.append((pos, len(piece), hashlib.sha256(piece).hexdigest()))
        pos += len(piece)
    path = tmp_path / name
    path.write_bytes(gzip.compress(b"".join(pieces), mtime=0))
    return str(path), chunks, *committed_layer(storage, str(path), chunks)


@pytest.mark.parametrize("in_flight", [1, 2])
def test_publication_hand_off_holds_the_pass_at_its_bound(
        tmp_path, monkeypatch, in_flight):
    """With the publisher's thread held inside its first pack, the
    pass fills IN_FLIGHT more, blocks handing over the last of them,
    and slices nothing further: never more than IN_FLIGHT + 1 packs of
    new bytes outside the stream."""
    import random
    import threading
    from makisu_tpu.cache.chunks import ChunkStore
    monkeypatch.setenv("MAKISU_TPU_PACK_TARGET_MB", "1")
    monkeypatch.setattr(recipe_mod.Publication, "IN_FLIGHT", in_flight)
    rng = random.Random(31)
    per_pack = 16                       # 16 x 64,000 >= 1,000,000
    pieces = [rng.randbytes(64_000) for _ in range(per_pack * 6)]
    storage = str(tmp_path / "storage")
    blob, chunks, pair, _ = _fed_layer(tmp_path, storage, pieces, "l.gz")
    store = ChunkStore(os.path.join(storage, "chunks"))
    rs = serve_server_mod.register_store(storage)
    held, entered = threading.Event(), threading.Event()
    sealed = []
    real_seal = recipe_mod.Publication._seal_pack

    def holding_seal(self, raw, members):
        entered.set()
        assert held.wait(timeout=60)
        sealed.append(len(raw))
        real_seal(self, raw, members)

    monkeypatch.setattr(recipe_mod.Publication, "_seal_pack", holding_seal)
    publication = rs.begin(pair, chunks, "", store,
                           thread_name="recipepub-held")
    seen = []

    def observer(fp, data):
        seen.append(fp)
        publication.feed(fp, data)

    failed = []

    def the_pass():
        try:
            store.index_layer(blob, chunks, observer=observer)
            publication.finish()
        except BaseException as e:  # noqa: BLE001 - reported below
            failed.append(e)

    feeder = threading.Thread(target=the_pass, daemon=True)
    feeder.start()
    assert entered.wait(timeout=60)
    want = per_pack * (in_flight + 1)   # one sealing, the rest waiting
    deadline = time.monotonic() + 60
    while len(seen) < want and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.3)                     # room to overrun, were there any
    assert len(seen) == want and feeder.is_alive()
    # In hand: the pack that cannot be handed over, and nothing else.
    assert publication._size == per_pack * 64_000
    assert publication._jobs.qsize() == in_flight - 1
    held.set()
    feeder.join(timeout=60)
    publication.thread.join(timeout=60)
    assert not feeder.is_alive() and not publication.thread.is_alive()
    assert not failed
    assert sealed == [per_pack * 64_000] * 6
    assert recipe_mod.verify(publication.doc, key=b"")
    assert len(publication.doc["packs"]) == 6


@pytest.mark.parametrize("builders", [2, 12])
@pytest.mark.parametrize("shared", ["all", "half"])
def test_builds_publishing_shared_new_chunks_at_once(
        tmp_path, monkeypatch, shared, builders):
    """Two builds (and twelve, more than the cores, under a switch
    interval that interleaves them) over one storage index and publish
    layers that share new chunks at the same moment: every publication
    finds them novel and packs them, the index keeps the first, and
    every row of every recipe resolves to bytes that hash to its
    fingerprint."""
    import hashlib
    import random
    import sys
    import threading
    monkeypatch.setenv("MAKISU_TPU_PACK_TARGET_MB", "1")
    rng = random.Random(32)
    common = [rng.randbytes(rng.randrange(2_000, 66_000))
              for _ in range(60)]
    storage = str(tmp_path / "storage")
    layers = []
    for k in range(builders):
        own = [rng.randbytes(rng.randrange(2_000, 66_000))
               for _ in range(0 if shared == "all" else 30)]
        pieces = own[:15] + common + own[15:]
        if shared == "all":
            pieces = pieces[7 * k:] + pieces[:7 * k]   # another order
        layers.append(_fed_layer(tmp_path, storage, pieces, f"l{k}.gz"))
    # One worker, one storage: the builds share its chunk store and its
    # recipe store, each with a pass and a publication of its own.
    from makisu_tpu.cache.chunks import ChunkStore, register_serving_store
    store = ChunkStore(os.path.join(storage, "chunks"))
    register_serving_store(store)
    rs = serve_server_mod.register_store(storage)
    start = threading.Barrier(builders)
    failed = []

    def build(k):
        try:
            blob, chunks, pair, _ = layers[k]
            start.wait(timeout=60)
            publication = rs.begin(pair, chunks, "", store,
                                   thread_name=f"recipepub-{k}")
            store.index_layer(blob, chunks, observer=publication.feed)
            publication.finish()
            publication.thread.join(timeout=60)
            assert not publication.thread.is_alive()
        except BaseException as e:  # noqa: BLE001 - reported below
            failed.append(e)

    builds = [threading.Thread(target=build, args=(k,))
              for k in range(builders)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in builds:
            t.start()
        for t in builds:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not failed and not any(t.is_alive() for t in builds)
    for _, chunks, pair, _ in layers:
        doc = rs.recipe(pair.gzip_descriptor.digest.hex())
        assert doc is not None and recipe_mod.verify(doc, key=b"")
        assert [(row[0], row[1]) for row in doc["chunks"]] == [
            (h, n) for _, n, h in chunks]
        for fp, length, pack_hex, pack_off in doc["chunks"]:
            data = b"".join(rs.iter_pack_range(
                pack_hex, pack_off, pack_off + length))
            assert hashlib.sha256(data).hexdigest() == fp
            assert doc["packs"][pack_hex] == rs.pack_size(pack_hex)
