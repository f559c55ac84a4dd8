"""The ``small-files`` configuration as it is shipped
(``perfbench/configs/small-files.json``): the generator gives the shapes
the file states, the same tree at 300 files builds through the program's
normal entry and is held to the benchmark's own reference, the CPU
hasher agrees with it, the layer-entries counter counts what the tar
holds, and the readers this configuration brought read a run record.
"""

import json
import os
import sys

import numpy as np
import pytest

from conftest import cas_entry_path

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
PERFBENCH = os.path.join(CHECKOUT, "perfbench")
sys.path.insert(0, PERFBENCH)

from pbharness import cells, check, driver, gen  # noqa: E402

from makisu_tpu import cli  # noqa: E402
from makisu_tpu.utils import metrics  # noqa: E402


def _load(*parts):
    with open(os.path.join(CHECKOUT, *parts), encoding="utf-8") as f:
        return json.load(f)


CONFIG = _load("perfbench", "configs", "small-files.json")
BENCHMARK = _load("BENCHMARK.json")
EDIT = _load("perfbench", "traffic", "edit.json")
CELL = "small-files-edit"
NEW_READERS = ("copy_checksum_s_per_build", "layer_scan_s_per_build",
               "layer_entries_per_build", "sha_lane_fill_pct")
ENTRIES = "makisu_layer_entries_total"

# tar's framing, as the program writes it: a 512-byte header an entry,
# content padded to 512, two closing blocks, the whole padded to
# tarfile's 10,240-byte record.
_BLOCK, _RECORD = 512, 10240
# chunker/cdc.py BLOCK and the 64 KiB the gear kernel's last block is
# padded to (ops/gear_pallas.py ROW * ROW_TILE): one compiled shape a
# quantum.
_GEAR_BLOCK, _GEAR_QUANTUM = 4 << 20, 64 << 10


def _tar_bytes(plan, dest_dirs=1):
    dirs = len({os.path.dirname(e["path"]) for e in plan}) + dest_dirs
    raw = sum(-(-e["size"] // _BLOCK) * _BLOCK for e in plan) \
        + _BLOCK * (len(plan) + dirs) + 2 * _BLOCK
    return -(-raw // _RECORD) * _RECORD, raw


# -- (a) the shapes the file states ----------------------------------------


@pytest.fixture(scope="module")
def sizes():
    return np.array([e["size"] for e in gen.file_plan(CONFIG["context"])])


def _files(sizes):
    [layer] = CONFIG["context"]["layers"]
    assert len(sizes) == CONFIG["files"] == layer["files"]
    assert CONFIG["files"] in (5000, 2500)


def _total_bytes(sizes):
    [layer] = CONFIG["context"]["layers"]
    assert int(sizes.sum()) == CONFIG["total_bytes"] == layer["bytes"]
    assert abs(CONFIG["total_bytes"] - 20000 * CONFIG["files"]) <= 65536


def _mean(sizes):
    want = CONFIG["source_scale"]["mean_file_bytes"]
    assert want == 20000 == (CONFIG["source_scale"]["total_bytes"]
                             // CONFIG["source_scale"]["files"])
    assert abs(sizes.mean() - want) / want < 0.001


def _mostly_under_the_minimum_chunk(sizes):
    assert 0.60 <= float((sizes < 2048).mean()) <= 0.80
    assert float(np.median(sizes)) < 2048


def _files_an_edit_can_draw(sizes):
    big = sizes[sizes >= EDIT["edit"]["min_file_bytes"]]
    assert len(big) >= 100
    # Nearly all bytes in a few files: node_modules' shape.
    assert big.sum() > 0.5 * sizes.sum()


def _tar_sits_past_a_gear_quantum(sizes):
    """The edit chain (1,000 bytes a build, the tar growing by 10,240
    about every tenth) must not reach the next compiled shape of the
    gear scan's last block inside priming and a window."""
    tar, raw = _tar_bytes(gen.file_plan(CONFIG["context"]))
    past = tar % _GEAR_BLOCK % _GEAR_QUANTUM
    assert past >= 2048
    room = _GEAR_QUANTUM - past + (tar - raw)
    # Four times what 2 priming builds and a window of 7 can add.
    assert room >= 40 << 10


@pytest.mark.parametrize("shape", [
    _files, _total_bytes, _mean, _mostly_under_the_minimum_chunk,
    _files_an_edit_can_draw, _tar_sits_past_a_gear_quantum],
    ids=lambda f: f.__name__.strip("_"))
def test_generator_gives_the_shapes_the_file_states(sizes, shape):
    shape(sizes)


def _entry_in_benchmark():
    [entry] = [c for c in BENCHMARK["configs"] if c["name"] == "small-files"]
    assert entry["file"] == "perfbench/configs/small-files.json"
    assert entry["reduced"] == ["files", "total_bytes"]
    [cell] = [w for w in BENCHMARK["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("small-files", "edit", 1)
    assert [w["name"] for w in BENCHMARK["workloads"]
            if w["config"] == "small-files"] == [CELL]


def _states_what_a_deployment_states():
    for key in ("source", "source_scale", "reduced_why", "assumed",
                "guarantees", "deployment"):
        assert CONFIG[key], key
    assert CONFIG["guarantees"] == _load(
        "perfbench", "configs", "monorepo-slice.json")["guarantees"]
    for gap in ("symlinks", "empty files", "deeper", "repeated"):
        assert gap in CONFIG["assumed"]["not_generated"]
    assert CONFIG["build_flags"] == ["--hasher", "tpu"]
    assert (CONFIG["lanes"], CONFIG["templates"], CONFIG["reference"],
            CONFIG["worker"]) == (1, 0, "cdc", {"max_concurrent_builds": 0})


def _cell_reports_its_metrics():
    cell = cells.Cell(os.path.join(CHECKOUT, "BENCHMARK.json"), CELL)
    assert {m["name"] for m in cell.end_to_end()} \
        == {"build_p50_s", "stored_per_user_byte", "setup_s"}
    mine = {m["name"] for m in cell.per_layer()}
    assert set(NEW_READERS) <= mine
    # The cell where the sink's read-ahead engages most (PR 40).
    assert {"sink_prefetch_ready_pct", "read_wait_s_per_build",
            "tar_write_s_per_build", "compress_wait_s_per_build"} <= mine
    # No cached layer here, and the four PERF.md marks "to be retired".
    assert not mine & {"apply_layer_s_per_build", "sync_wait_share_pct",
                       "commit_share_pct", "device_mb_per_build",
                       "chunk_store_share_pct"}
    for name in mine:
        assert callable(cell.reader(name))


@pytest.mark.parametrize("statement", [
    _entry_in_benchmark, _states_what_a_deployment_states,
    _cell_reports_its_metrics], ids=lambda f: f.__name__.strip("_"))
def test_configuration_and_cell_are_declared(statement):
    statement()


# -- (b) (c) (d): the same tree at 300 files, through the program ----------


def _scaled_context():
    context = json.loads(json.dumps(CONFIG["context"]))
    [layer] = context["layers"]
    layer["files"], layer["bytes"] = 300, 3_000_000
    context["fanout"] = 37
    return context


def _build(work, context_dir, tag, hasher, storage, spy=None):
    root = os.path.join(work, f"root-{tag}")
    os.makedirs(root)
    report = os.path.join(work, f"report-{tag}.json")
    b = driver.Build(lane=0, index=0, kind="cold", tag=f"smallfiles/t:{tag}",
                     context=context_dir, storage=storage, context_bytes=0)
    with pytest.MonkeyPatch.context() as mp:
        if spy is not None:
            real = metrics.counter_add

            def counting(name, value=1.0, **labels):
                spy.append((name, value, labels))
                return real(name, value, **labels)
            mp.setattr(metrics, "counter_add", counting)
        b.exit_code = cli.main([
            "--log-level", "error", "--metrics-out", report, "build",
            context_dir, "-t", b.tag, "--storage", storage, "--root", root,
            "--hasher", hasher])
    b.terminal = {"exit_code": b.exit_code}
    with open(report, encoding="utf-8") as f:
        return b, json.load(f)


def _digests(b):
    """(blob digests, tar digests) as the build's manifest and image
    config state them, read as the check reads them."""
    manifest, config, _ = check.Checker(None, {})._manifest(b)
    return ([layer["digest"] for layer in manifest["layers"]],
            config["rootfs"]["diff_ids"])


def _held_to_reference(reference, context, b):
    checker = check.Checker(reference, context)
    checker.check_build(b, tree_is_current=True)
    return checker


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    work = str(tmp_path_factory.mktemp("smallfiles"))
    context = _scaled_context()
    reference = cells._load_module(
        os.path.join(PERFBENCH, "reference", CONFIG["reference"] + ".py"))
    out = {"context": context, "reference": reference,
           "plan": gen.file_plan(context)}
    trees = {}
    for seed in (1, 2):
        trees[seed] = os.path.join(work, f"ctx{seed}")
        gen.make_tree(context, trees[seed], seed)
    out["trees"] = trees
    ctx = trees[1]
    storage = os.path.join(work, "storage-tpu")
    out["spy"] = []
    cold, out["cold_report"] = _build(work, ctx, "cold", "tpu", storage,
                                      spy=out["spy"])
    out["cold"] = cold
    out["cold_check"] = _held_to_reference(reference, context, cold)
    hexd = _digests(cold)[0][0].split(":", 1)[1]
    out["cold_tar"] = reference.inflate(
        cas_entry_path(os.path.join(storage, "layers"), hexd))
    out["touched"] = gen.apply_edit(EDIT["edit"], context, ctx,
                                    np.random.default_rng([1, 0, 7]), "000001")
    edited, _ = _build(work, ctx, "edited", "tpu", storage)
    out["edited"] = edited
    out["edited_check"] = _held_to_reference(reference, context, edited)
    out["cpu"], _ = _build(work, ctx, "cpu", "cpu",
                           os.path.join(work, "storage-cpu"))
    return out


def test_two_content_seeds_give_equal_sizes_and_other_bytes(built):
    listing = {}
    for seed, root in built["trees"].items():
        listing[seed] = {p[len(root):]: os.path.getsize(p)
                         for p in gen.layer_files(built["context"], root,
                                                  "last")}
    # Seed 1's tree has had its edit by now: one file 1,000 bytes longer.
    differing = [p for p in listing[2] if listing[1][p] != listing[2][p]]
    assert len(differing) == built["touched"] == 1
    assert listing[1][differing[0]] - listing[2][differing[0]] \
        == EDIT["edit"]["bytes"]
    assert sorted(listing[2].values()) \
        == sorted(e["size"] for e in built["plan"])
    same = 0
    for rel in list(listing[2])[:40]:
        with open(built["trees"][1] + rel, "rb") as f1, \
                open(built["trees"][2] + rel, "rb") as f2:
            same += f1.read() == f2.read()
    assert same == 0


@pytest.mark.parametrize("count", sorted(check.LIMITS))
@pytest.mark.parametrize("which", ["cold", "edited"])
def test_build_held_to_the_reference(built, which, count):
    assert built[which].exit_code == 0
    checker = built[which + "_check"]
    assert checker.found[count] == 0, checker.notes
    assert checker.checked["builds"] == 1 and checker.checked["layers"] == 1
    assert checker.checked["members"] == 300
    assert checker.checked["chunks"] > 200


def test_edit_moved_the_layer_and_kept_nearly_every_chunk(built):
    cold, edited = _digests(built["cold"]), _digests(built["edited"])
    assert cold != edited
    from makisu_tpu.storage.cas import CASDir
    stored = len(CASDir(os.path.join(built["cold"].storage,
                                     "chunks")).keys())
    assert stored <= built["cold_check"].checked["chunks"] + 8


def test_tar_is_as_long_as_the_framing_says(built):
    """The arithmetic that sizes the shipped layer past a gear quantum,
    held to a tar the program wrote."""
    tar, _ = _tar_bytes(built["plan"])
    assert len(built["cold_tar"]) == tar


def test_cpu_hasher_gives_the_same_layer_and_blob_digests(built):
    assert built["cpu"].exit_code == 0
    assert _digests(built["cpu"]) == _digests(built["edited"])


def _members_by_kind(built):
    ref = built["reference"]
    kinds = {}
    for member in ref.tar_members(built["cold_tar"]).values():
        kind = {ref.REGTYPE: "file", b"5": "dir", b"2": "symlink"}.get(
            member[0], "other")
        kinds[kind] = kinds.get(kind, 0) + 1
    return kinds


def _counter_grows_by_the_tars_members(built):
    want = _members_by_kind(built)
    assert want == {"file": 300, "dir": 38}
    got = {s["labels"]["kind"]: s["value"]
           for s in built["cold_report"]["counters"][ENTRIES]}
    assert got == want


def _one_add_a_kind_a_layer(built):
    adds = [(labels, value) for name, value, labels in built["spy"]
            if name == ENTRIES]
    # One layer, two kinds in it: two adds, never one an entry.
    assert sorted(labels["kind"] for labels, _ in adds) == ["dir", "file"]
    assert sum(value for _, value in adds) == 338


@pytest.mark.parametrize("claim", [
    _counter_grows_by_the_tars_members, _one_add_a_kind_a_layer],
    ids=lambda f: f.__name__.strip("_"))
def test_layer_entries_counter(built, claim):
    claim(built)


# -- (e) the readers, on a run record made by hand -------------------------


def _series(name, value, **labels):
    return (name, tuple(sorted(labels.items()))), value


def _record(tmp_path, with_program_side):
    def counted(spans, ok=True):
        b = driver.Build(lane=0, index=0, kind="rebuild", tag="", context="",
                         storage="", context_bytes=1,
                         exit_code=0 if ok else 1, terminal={"x": 1})
        b.spans = spans
        return b
    r = driver.Run(cell=None, seed=1, seconds=45.0, trace=True,
                   work_dir=str(tmp_path))
    if with_program_side:
        spans = [("copy_checksum", 0.5), ("layer_scan", 0.25),
                 ("layer_scan", 0.75), ("tar_write", 3.0)]
    else:
        spans = [("commit_layer", 2.0)]
    r.counted = [counted(spans), counted(spans),
                 counted([("layer_scan", 99.0)], ok=False)]
    r.builds = list(r.counted)
    hashed, moved = "makisu_bytes_hashed_total", \
        "makisu_device_transfer_bytes_total"
    r.counters_open = dict([
        _series(ENTRIES, 5500.0, kind="file"),
        _series(hashed, 100e6, backend="pallas", path="service"),
        _series(hashed, 100e6, backend="native", path="layer_sink"),
        _series(moved, 400e6, direction="h2d", stage="sha")])
    r.counters_close = dict([
        _series(ENTRIES, 20500.0, kind="file"),
        _series(ENTRIES, 1500.0, kind="dir"),
        _series(hashed, 400e6, backend="pallas", path="service"),
        _series(hashed, 30e6, backend="pallas", path="cdc"),
        _series(hashed, 400e6, backend="native", path="layer_sink"),
        _series(moved, 1720e6, direction="h2d", stage="sha"),
        _series(moved, 900e6, direction="h2d", stage="gear")])
    if not with_program_side:
        old = dict([_series("makisu_device_h2d_bytes_total", 8.0,
                            bucket="16384")])
        r.counters_open, r.counters_close = dict(old), dict(old)
    return r


# Spans: summed over the two builds that ended well, over 2. Counters:
# growth over the window, over the 3 counted.
@pytest.mark.parametrize("metric,want", [
    ("copy_checksum_s_per_build", (0.5 + 0.5) / 2),
    ("layer_scan_s_per_build", (1.0 + 1.0) / 2),
    ("layer_entries_per_build", (15000 + 1500) / 3),
    ("sha_lane_fill_pct", 100.0 * (300 + 30) / 1320),
])
def test_new_reader_reads_a_run_and_nothing_from_an_older_program(
        tmp_path, metric, want):
    read = cells._load_module(
        os.path.join(PERFBENCH, "readers", metric + ".py")).read
    assert read(_record(tmp_path, True)) == pytest.approx(want)
    assert read(_record(tmp_path, False)) is None
    untraced = _record(tmp_path, False)
    untraced.counters_open = untraced.counters_close = None
    assert read(untraced) is None


# -- a storage directory the parent commit wrote (PR 49) --------------------


def _as_the_parent_wrote_it(chunks_root):
    """Rewrite a chunk store into the layout before segments: one file
    an entry at ``<aa>/<name>``, mode 0600, an empty ``_tmp/``, nothing
    else. The owner of the layout is asked for every place."""
    import shutil

    from makisu_tpu.storage.cas import CASDir
    bare = CASDir(chunks_root)
    held = {name: bare.read(name) for name in bare.keys()}
    shutil.rmtree(bare._seg_dir)
    for name, data in held.items():
        path = cas_entry_path(chunks_root, name)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(data)
        os.chmod(path, 0o600)
    return held


@pytest.mark.parametrize("count", sorted(check.LIMITS))
def test_storage_the_parent_wrote_builds_dedups_and_checks(
        parents_storage, count):
    assert parents_storage["edited"].exit_code == 0
    checker = parents_storage["check"]
    assert checker.found[count] == 0, checker.notes
    assert checker.checked["chunks"] > 200


@pytest.fixture(scope="module")
def parents_storage(tmp_path_factory):
    """A cold build, its chunk store rewritten as the parent commit's
    ``write_many`` left one, then an edit built on top of it."""
    from makisu_tpu.storage.cas import CASDir
    work = str(tmp_path_factory.mktemp("parentstore"))
    context = _scaled_context()
    reference = cells._load_module(
        os.path.join(PERFBENCH, "reference", CONFIG["reference"] + ".py"))
    ctx = os.path.join(work, "ctx")
    gen.make_tree(context, ctx, 3)
    storage = os.path.join(work, "storage")
    cold, _ = _build(work, ctx, "cold", "tpu", storage)
    assert cold.exit_code == 0
    chunks_root = os.path.join(storage, "chunks")
    out = {"held": _as_the_parent_wrote_it(chunks_root)}
    out["tree"] = sorted(
        os.path.relpath(os.path.join(parent, fn), chunks_root)
        for parent, _, files in os.walk(chunks_root) for fn in files)
    gen.apply_edit(EDIT["edit"], context, ctx,
                   np.random.default_rng([3, 0, 7]), "000001")
    out["edited"], out["report"] = _build(work, ctx, "edited", "tpu",
                                          storage)
    out["check"] = _held_to_reference(reference, context, out["edited"])
    out["chunks_root"] = chunks_root
    out["after"] = CASDir(chunks_root)
    return out


def test_storage_the_parent_wrote_keeps_its_files_and_gains_a_segment(
        parents_storage):
    """Dedup found the parent's loose files (a handful of new chunks,
    not a layer's worth), left every one of them where it was, and
    stored the new ones in a segment beside them."""
    held, after = parents_storage["held"], parents_storage["after"]
    new = set(after.keys()) - set(held)
    assert 1 <= len(new) <= 8
    assert all(after.read(name) == data for name, data in held.items())
    root = parents_storage["chunks_root"]
    tree = sorted(os.path.relpath(os.path.join(parent, fn), root)
                  for parent, _, files in os.walk(root) for fn in files)
    added = [rel for rel in tree if rel not in parents_storage["tree"]]
    assert len(parents_storage["tree"]) == len(held)
    # One segment and its index; a second pair the rare time another
    # writer of the build (a session shard's put) held the first.
    assert len(added) in (2, 4)
    assert all(rel.endswith((".seg", ".idx")) for rel in added)
    assert all("@" in after.where(name) for name in new)
    ingest = {s["labels"]["result"]: s["value"] for s in
              parents_storage["report"]["counters"][metrics.CHUNK_INGEST]}
    assert ingest["written"] == len(new)
    assert ingest["present"] >= len(held) - 8
    created = {s["labels"]["kind"]: s["value"] for s in
               parents_storage["report"]["counters"][
                   metrics.CHUNK_STORE_FILES_CREATED]}
    assert created == {"segment": len(added) / 2, "index": len(added) / 2,
                       "loose": 0.0}
