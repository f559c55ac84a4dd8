"""The ``huge-layer`` configuration as it is shipped
(``perfbench/configs/huge-layer.json``): the generator gives the two
files and the tar length the file states (at full size, no content
written), the same tree at 2 x 3 MiB builds through the program's
normal entry and is held to the configuration's own reference
(``perfbench/reference/cdc_slab.py``), the CPU hasher agrees with it,
the slab reference equals the plain one, the readers this configuration
brought read a run record, and the peak-RSS gauge is in ``/metrics``.
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
from makisu_tpu.utils import metrics, resources  # noqa: E402
from makisu_tpu.worker import WorkerClient, WorkerServer  # noqa: E402


def _load(*parts):
    with open(os.path.join(CHECKOUT, *parts), encoding="utf-8") as f:
        return json.load(f)


def _module(*parts):
    return cells._load_module(os.path.join(PERFBENCH, *parts))


CONFIG = _load("perfbench", "configs", "huge-layer.json")
BENCHMARK = _load("BENCHMARK.json")
EDIT = _load("perfbench", "traffic", "edit.json")
CELL = "huge-layer-edit"
NEW_READERS = ("commit_mb_per_s", "compress_s_per_build",
               "feed_host_s_per_build", "process_rss_peak_mb")
PEAK = "makisu_process_peak_rss_bytes"
SLAB = _module("reference", "cdc_slab.py")
PLAIN = _module("reference", "cdc.py")

# tar's framing, as the program writes it: a 512-byte header an entry,
# content padded to 512, two closing blocks, the whole padded to
# tarfile's 10,240-byte record.
_BLOCK, _RECORD = 512, 10240
# chunker/cdc.py BLOCK and the 64 KiB the gear kernel's last block is
# padded to: one compiled shape a quantum.
_GEAR_BLOCK, _GEAR_QUANTUM = 4 << 20, 64 << 10


def _tar_bytes(plan, dest_dirs=1):
    dirs = len({os.path.dirname(e["path"]) for e in plan}) + dest_dirs
    raw = sum(-(-e["size"] // _BLOCK) * _BLOCK for e in plan) \
        + _BLOCK * (len(plan) + dirs) + 2 * _BLOCK
    return -(-raw // _RECORD) * _RECORD, raw


# -- (a) the shapes the file states, at full size --------------------------


def _two_files_of_half_the_bytes(plan):
    [layer] = CONFIG["context"]["layers"]
    assert [e["size"] for e in plan] == [CONFIG["file_bytes"]] * 2
    assert CONFIG["files"] == layer["files"] == 2
    assert CONFIG["total_bytes"] == layer["bytes"] == 2 * CONFIG["file_bytes"]
    # The size ISSUE 30 names, or its one allowed halving.
    assert CONFIG["file_bytes"] in (134217728, 67108864)
    assert [e["path"] for e in plan] == ["model/d00/f00000.bin",
                                         "model/d01/f00001.bin"]


def _one_incompressible_one_text(plan):
    assert [e["kind"] for e in plan] == ["random", "text"]
    assert CONFIG["context"]["content"] == ["random", "text"]


def _both_files_can_be_drawn_by_the_edit(plan):
    assert all(e["size"] >= EDIT["edit"]["min_file_bytes"] for e in plan)


def _tar_is_as_long_as_the_file_states(plan):
    tar, _ = _tar_bytes(plan)
    assert tar == CONFIG["layer_tar_bytes"]
    assert f"{tar:,}" in CONFIG["assumed"]["tar"]


def _tar_sits_past_a_gear_quantum(plan):
    """The edit chain (1,000 bytes a build) must not reach the next
    compiled shape of the gear scan's last block inside a run."""
    tar, raw = _tar_bytes(plan)
    past = tar % _GEAR_BLOCK % _GEAR_QUANTUM
    assert 1024 <= past <= 40 << 10
    # Priming and a window make under 10 edits; room for four times that.
    assert _GEAR_QUANTUM - past + (tar - raw) >= 40 * EDIT["edit"]["bytes"]


@pytest.mark.parametrize("shape", [
    _two_files_of_half_the_bytes, _one_incompressible_one_text,
    _both_files_can_be_drawn_by_the_edit, _tar_is_as_long_as_the_file_states,
    _tar_sits_past_a_gear_quantum], ids=lambda f: f.__name__.strip("_"))
def test_generator_gives_the_shapes_the_file_states(shape):
    shape(gen.file_plan(CONFIG["context"]))


def _entry_in_benchmark():
    [entry] = [c for c in BENCHMARK["configs"] if c["name"] == "huge-layer"]
    assert entry["file"] == "perfbench/configs/huge-layer.json"
    assert entry["reduced"] == ["file_bytes", "total_bytes"]
    assert len(entry["source"]) <= 200
    [cell] = [w for w in BENCHMARK["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("huge-layer", "edit", 1)
    assert [w["name"] for w in BENCHMARK["workloads"]
            if w["config"] == "huge-layer"] == [CELL]


def _states_what_a_deployment_states():
    for key in ("source", "source_scale", "reduced_why", "assumed",
                "guarantees", "deployment"):
        assert CONFIG[key], key
    assert CONFIG["guarantees"] == _load(
        "perfbench", "configs", "monorepo-slice.json")["guarantees"]
    assert CONFIG["source_scale"] == {
        "files": 2, "file_bytes": "1-2 GiB", "total_bytes": "2-4 GiB"}
    for gap in ("code layer", "base image", "4 GiB"):
        assert gap in CONFIG["assumed"]["not_generated"]
    assert CONFIG["build_flags"] == ["--hasher", "tpu"]
    assert (CONFIG["lanes"], CONFIG["templates"], CONFIG["reference"],
            CONFIG["worker"]) == (1, 0, "cdc_slab",
                                  {"max_concurrent_builds": 0})
    context = CONFIG["context"]
    assert context["dockerfile"] == "FROM scratch\nCOPY model /model/\n"
    assert (context["size_seed"], context["fanout"]) == (4, 2)
    assert context["sizes"]["lo"] == context["sizes"]["hi"]


def _cell_reports_its_metrics():
    cell = cells.Cell(os.path.join(CHECKOUT, "BENCHMARK.json"), CELL)
    assert cell.reference.__name__ == "perfbench_cdc_slab"
    assert {m["name"] for m in cell.end_to_end()} \
        == {"build_p50_s", "stored_per_user_byte", "setup_s"}
    mine = {m["name"] for m in cell.per_layer()}
    assert set(NEW_READERS) <= mine
    assert {"sha_hbm_roofline", "gear_hbm_roofline", "tar_write_s_per_build",
            "chunk_index_s_per_build", "sha_lane_fill_pct"} <= mine
    # One layer, nothing replayed; no farm metric; none "to be retired".
    assert not mine & {"apply_layer_s_per_build", "sync_wait_share_pct",
                       "commit_share_pct", "device_mb_per_build",
                       "chunk_store_share_pct", "queue_wait_p50_s",
                       "hash_batch_occupancy_pct"}
    # Two files of 64 MiB: the sink's readers get none (PR 40).
    assert not mine & {"sink_prefetch_ready_pct", "read_wait_s_per_build"}
    for name in mine:
        assert callable(cell.reader(name))


def _new_metrics_list_their_cells():
    cells_of = {m["name"]: m["workloads"] for m in BENCHMARK["per_layer"]}
    # PR 32 appended its cell to the three it reports.
    # PR 47 appended the same image's pgzip cell to what this one
    # reports.
    pgzip = "huge-layer-pgzip-edit"
    # PR 50 appended its cell: eight such commits at once.
    farm = "monorepo-farm-churn"
    four = [CELL, "monorepo-cold", "monorepo-edit", "small-files-edit",
            "multi-stage-small-edit", pgzip, farm]
    assert cells_of["commit_mb_per_s"] == four
    assert cells_of["compress_s_per_build"] == four
    assert cells_of["feed_host_s_per_build"] == four
    # PR 38 appended its cell: 32 sinks' rings at once.
    assert cells_of["process_rss_peak_mb"] == [
        CELL, "monorepo-cold", "small-files-edit", "farm-concurrent-churn",
        pgzip, farm]
    # Put at the end of the list at their PR, together and in order;
    # later PRs append after them.
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    first = names.index(NEW_READERS[0])
    assert names[first:first + 4] == list(NEW_READERS)
    assert cells_of["chunk_probe_hit_pct"] == [
        CELL, "monorepo-edit", "monorepo-cold", "small-files-edit",
        "multi-stage-small-edit", pgzip, farm]
    # PR 33: what the builder waits for the sink's compressor thread,
    # read where ``compress_s_per_build`` is, appended last.
    assert cells_of["compress_wait_s_per_build"] == four
    wait = BENCHMARK["per_layer"][names.index("compress_wait_s_per_build")]
    beside = BENCHMARK["per_layer"][names.index("compress_s_per_build")]
    assert {**wait, "name": beside["name"]} == beside


@pytest.mark.parametrize("statement", [
    _entry_in_benchmark, _states_what_a_deployment_states,
    _cell_reports_its_metrics, _new_metrics_list_their_cells],
    ids=lambda f: f.__name__.strip("_"))
def test_configuration_and_cell_are_declared(statement):
    statement()


# -- (b) the slab reference equals the plain one ---------------------------


# With a slab of 8,192 bytes: no byte, under a segment, one slab to the
# byte and one more, several slabs with a ragged end.
@pytest.mark.parametrize("n", [0, 1, 33, 4095, 8191, 8192, 8193,
                               3 * 8192 + 17, 70001])
@pytest.mark.parametrize("avg_bits", [13, 6])
def test_slab_candidates_equal_the_plain_ones(n, avg_bits):
    data = np.random.default_rng([n, avg_bits]).bytes(n)
    want = PLAIN.candidates(data, avg_bits)
    for slab in (4096, 8192, SLAB.SLAB):
        got = SLAB.candidates(data, avg_bits, slab=slab)
        assert got.dtype == np.int64
        assert np.array_equal(got, want), slab
    if avg_bits == 6:
        assert n < 4096 or len(want) > n // 128


def test_slab_cut_points_equal_the_plain_ones_and_module_is_independent():
    data = np.random.default_rng(30).bytes(300_000)
    assert SLAB.cut_points(data) == PLAIN.cut_points(data)
    # A run of one byte has no candidate: cuts at the maximum size.
    assert SLAB.cut_points(bytes(200_000)) == PLAIN.cut_points(bytes(200_000))
    with open(os.path.join(PERFBENCH, "reference", "cdc_slab.py"),
              encoding="utf-8") as f:
        source = f.read()
    assert "import makisu_tpu" not in source
    assert "from makisu_tpu" not in source
    for name in ("inflate", "sha256_hex", "file_sha256_hex", "tar_members",
                 "tree_members", "REGTYPE", "gear_table"):
        assert getattr(SLAB, name) is getattr(SLAB._cdc, name)
    with pytest.raises(ValueError):
        SLAB.candidates(b"x" * 10, slab=1000)


# -- (c) the same tree at 2 x 3 MiB, through the program -------------------


def _scaled_context():
    context = json.loads(json.dumps(CONFIG["context"]))
    [layer] = context["layers"]
    layer["bytes"] = 2 * (3 << 20)
    return context


def _build(work, context_dir, tag, hasher, storage):
    root = os.path.join(work, f"root-{tag}")
    os.makedirs(root)
    report = os.path.join(work, f"report-{tag}.json")
    b = driver.Build(lane=0, index=0, kind="cold", tag=f"hugelayer/t:{tag}",
                     context=context_dir, storage=storage, context_bytes=0)
    b.exit_code = cli.main([
        "--log-level", "error", "--metrics-out", report, "build",
        context_dir, "-t", b.tag, "--storage", storage, "--root", root,
        "--hasher", hasher])
    b.terminal = {"exit_code": b.exit_code}
    with open(report, encoding="utf-8") as f:
        return b, json.load(f)


def _digests(b):
    manifest, config, _ = check.Checker(None, {})._manifest(b)
    return ([layer["digest"] for layer in manifest["layers"]],
            config["rootfs"]["diff_ids"])


def _held_to_reference(context, b):
    checker = check.Checker(SLAB, context)
    checker.check_build(b, tree_is_current=True)
    return checker


def _stored_chunks(b):
    from makisu_tpu.storage.cas import CASDir
    return set(CASDir(os.path.join(b.storage, "chunks")).keys())


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    work = str(tmp_path_factory.mktemp("hugelayer"))
    context = _scaled_context()
    out = {"context": context, "plan": gen.file_plan(context)}
    ctx = os.path.join(work, "ctx")
    gen.make_tree(context, ctx, 1)
    storage = os.path.join(work, "storage-tpu")
    out["cold"], out["cold_report"] = _build(work, ctx, "cold", "tpu",
                                             storage)
    out["cold_check"] = _held_to_reference(context, out["cold"])
    out["cold_chunks"] = _stored_chunks(out["cold"])
    hexd = _digests(out["cold"])[0][0].split(":", 1)[1]
    out["cold_tar_len"] = len(SLAB.inflate(
        cas_entry_path(os.path.join(storage, "layers"), hexd)))
    out["touched"] = gen.apply_edit(EDIT["edit"], context, ctx,
                                    np.random.default_rng([1, 0, 7]), "000001")
    out["edited"], out["edited_report"] = _build(work, ctx, "edited", "tpu",
                                                 storage)
    out["edited_check"] = _held_to_reference(context, out["edited"])
    out["edited_chunks"] = _stored_chunks(out["edited"])
    out["cpu"], _ = _build(work, ctx, "cpu", "cpu",
                           os.path.join(work, "storage-cpu"))
    return out


def test_scaled_tree_is_two_files_of_half_the_bytes(built):
    assert [e["size"] for e in built["plan"]] == [3 << 20, 3 << 20]
    assert [e["kind"] for e in built["plan"]] == ["random", "text"]
    tar, _ = _tar_bytes(built["plan"])
    assert built["cold_tar_len"] == tar


@pytest.mark.parametrize("count", sorted(check.LIMITS))
@pytest.mark.parametrize("which", ["cold", "edited"])
def test_build_held_to_the_slab_reference(built, which, count):
    assert built[which].exit_code == 0
    checker = built[which + "_check"]
    assert checker.found[count] == 0, checker.notes
    assert checker.checked["builds"] == 1 and checker.checked["layers"] == 1
    assert checker.checked["members"] == 2
    assert checker.checked["chunks"] > 400


def _hashed(report, path):
    return sum(s["value"]
               for s in report["counters"]["makisu_bytes_hashed_total"]
               if s["labels"].get("path") == path)


def test_edit_refeeds_the_whole_layer_and_keeps_nearly_every_chunk(built):
    assert built["touched"] == 1
    assert _digests(built["cold"]) != _digests(built["edited"])
    tar, _ = _tar_bytes(built["plan"])
    # The whole tar went through the sink and the chunker again ...
    assert _hashed(built["edited_report"], "layer_sink") >= tar
    assert _hashed(built["edited_report"], "cdc") >= tar
    # ... and all but a handful of its chunks were already stored.
    new = built["edited_chunks"] - built["cold_chunks"]
    assert 1 <= len(new) <= 6
    assert len(built["cold_chunks"]) > 400


def test_cpu_hasher_gives_the_same_layer_and_blob_digests(built):
    assert built["cpu"].exit_code == 0
    assert _digests(built["cpu"]) == _digests(built["edited"])


def test_native_sink_reports_its_compress_seconds(built):
    """The sink a worker's ``--hasher tpu`` build commits through
    deflates on a thread of its own (PR 33): the ``compress`` stage is
    what that thread spent, beside ``tar_write`` and no longer a part
    of it, and ``compress_wait`` what the builder was blocked on it
    (the drain in ``finish`` at least)."""
    busy = {s["labels"]["stage"]: s["value"] for s in
            built["cold_report"]["counters"][metrics.COMMIT_STAGE_BUSY]}
    assert 0 < busy["compress"]
    assert 0 < busy["compress_wait"]
    assert 0 < busy["tar_write"]


# -- (d) the readers, on a run record made by hand -------------------------


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
    spans = [("commit_layer", 8.0), ("tar_write", 5.0)]
    r.counted = [counted(spans), counted(spans),
                 counted([("commit_layer", 99.0)], ok=False)]
    r.builds = list(r.counted)
    hashed, busy = "makisu_bytes_hashed_total", metrics.COMMIT_STAGE_BUSY
    probed = "makisu_chunk_exists_prefetch_total"
    r.counters_open = dict([
        _series(probed, 100.0, result="hit"),
        _series(probed, 50.0, result="probe"),
        _series(hashed, 100e6, backend="native", path="layer_sink"),
        _series(hashed, 100e6, backend="pallas", path="service"),
        _series(busy, 1.0, stage="compress"),
        _series(busy, 0.5, stage="compress_wait"),
        _series(busy, 2.0, stage="host_cut"),
        _series(PEAK, 900e6)])
    r.counters_close = dict([
        _series(probed, 1000.0, result="hit"),
        _series(probed, 90.0, result="miss"),
        _series(probed, 60.0, result="probe"),
        _series(hashed, 900e6, backend="native", path="layer_sink"),
        _series(hashed, 16e6, backend="python", path="layer_sink"),
        _series(hashed, 700e6, backend="pallas", path="service"),
        _series(busy, 13.0, stage="compress"),
        _series(busy, 6.5, stage="compress_wait"),
        _series(busy, 5.0, stage="host_cut"),
        _series(busy, 1.5, stage="gear_dispatch"),
        _series(busy, 0.75, stage="sha_dispatch"),
        _series(busy, 7.0, stage="gear_readback"),
        _series(PEAK, 1234e6)])
    if not with_program_side:
        for b in r.counted:
            b.spans = [("apply_layer", 0.5)]
        old = dict([_series("makisu_device_h2d_bytes_total", 8.0,
                            bucket="16384")])
        r.counters_open, r.counters_close = dict(old), dict(old)
    return r


# The sink's bytes grow over the window (all three counted builds fed
# it); the spans are summed over the two builds that ended well. Stage
# seconds: growth over the window, over the 3 counted. The peak is a
# level at the window's close.
@pytest.mark.parametrize("metric,want", [
    ("commit_mb_per_s", (800 + 16) / (8.0 + 8.0)),
    ("compress_s_per_build", 12.0 / 3),
    # PR 33: the builder's wait for the sink's compressor thread, a
    # series of its own beside ``compress`` (no part of it, nor of the
    # feed's or the device's stages).
    ("compress_wait_s_per_build", 6.0 / 3),
    ("feed_host_s_per_build", (3.0 + 1.5 + 0.75) / 3),
    ("process_rss_peak_mb", 1234.0),
    # PR 31: of the chunks index_layer looked up in the window (900 found
    # by the streamed probe, 90 looked for and absent, 10 never looked
    # for), the share found.
    ("chunk_probe_hit_pct", 90.0),
])
def test_new_reader_reads_a_run_and_nothing_from_an_older_program(
        tmp_path, metric, want):
    read = _module("readers", metric + ".py").read
    assert read(_record(tmp_path, True)) == pytest.approx(want)
    assert read(_record(tmp_path, False)) is None
    untraced = _record(tmp_path, False)
    untraced.counters_open = untraced.counters_close = None
    assert read(untraced) is None


# -- (e) the gauge ---------------------------------------------------------


def test_peak_rss_gauge_is_in_metrics_and_never_falls(tmp_path):
    server = WorkerServer(str(tmp_path / "worker.sock"))
    thread = server.serve_background()
    try:
        sampler = resources.ensure_started()
        client = WorkerClient(server.socket_path)
        seen = []
        ballast = None
        for step in range(3):
            if step == 1:
                ballast = bytearray(os.urandom(1 << 20) * 96)
            if step == 2:
                ballast = None     # the level falls, the mark does not
            sampler.sample_once()
            series = {}
            for line in client.metrics().splitlines():
                name, _, value = line.partition(" ")
                if name in (PEAK, "makisu_process_rss_bytes"):
                    series[name] = float(value)
            assert series[PEAK] >= series["makisu_process_rss_bytes"] > 0
            seen.append(series[PEAK])
        assert ballast is None
        assert seen == sorted(seen)
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=5)
