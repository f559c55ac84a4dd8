"""``perfbench/pbharness/hostspans.py`` on the small trace recorded
beside this file, and each reader PR 24 adds on a constructed run.

``hostspans_small.xplane.pb`` was recorded on the CPU with the
program's own ``metrics.span`` on the profiler's clock: a mark, then
lane A (``build`` > ``stage`` > ``step`` > ``commit_layer`` >
``tar_write`` with a bare ``gear_readback`` inside, then ``step`` alone,
then ``chunk_index``, then ``build`` alone), lane B (``build`` >
``stage``), and the hash service's thread (a bare ``sha_readback``, no
span). Seconds from the mark, read off the file when it was recorded:

    lane A  build 0.050244-0.450231   stage/step 0.0804-0.4002
            tar_write 0.100179-0.200184   gear_readback 0.140212-0.180185
            chunk_index 0.300227-0.400183
    lane B  build 0.250196-0.350427   stage 0.250209-0.350212
    service sha_readback 0.120199-0.220176
"""

import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "perfbench"))

from pbharness import cells, driver, hostspans, xplane  # noqa: E402

TRACE = os.path.join(HERE, "hostspans_small.xplane.pb")
MARK = "perfbench_window_open"
# Three device gaps: inside tar_write; across step-alone, lane B's
# start and chunk_index; over lane A's last stretch and past its end.
GAPS = [(0.11, 0.19), (0.22, 0.32), (0.42, 0.50)]
READERS = os.path.join(os.path.dirname(HERE), "perfbench", "readers")


def _reader(name):
    return cells._load_module(os.path.join(READERS, name + ".py")).read


@pytest.fixture(scope="module")
def recorded():
    return hostspans.host_events(TRACE, MARK)


def test_mark_found_and_events_relative_to_it(recorded):
    assert hostspans.host_events(TRACE, "no_such_mark") is None
    by_name = {}
    for ev in recorded:
        by_name.setdefault(ev.name, []).append(ev)
    assert sorted(by_name) == [
        "build", "chunk_index", "commit_layer", "gear_readback",
        "sha_readback", "stage", "step", "tar_write"]
    assert len({ev.thread for ev in recorded}) == 3
    [tar] = by_name["tar_write"]
    assert (tar.start_s, tar.end_s) == pytest.approx((0.100179, 0.200184),
                                                    abs=1e-6)
    assert tar.span_id and not by_name["gear_readback"][0].span_id
    a, b = sorted(by_name["build"], key=lambda ev: ev.start_s)
    assert a.thread == tar.thread != b.thread
    assert by_name["sha_readback"][0].thread not in (a.thread, b.thread)


def test_gap_seconds_go_to_the_innermost_span(recorded):
    charged, total = hostspans.charge_gaps(recorded, GAPS)
    # Gap 1: lane A alone (the service's thread has no span open).
    readback = 0.180185 - 0.140212
    assert charged["gear_readback"] == pytest.approx(readback, abs=1e-6)
    assert charged["tar_write"] == pytest.approx(0.08 - readback, abs=1e-6)
    assert "sha_readback" not in charged and "commit_layer" not in charged
    # Gap 2: lane A under step alone, then shared with lane B.
    alone = 0.250196 - 0.22
    b_root = 0.250209 - 0.250196      # lane B's build before its stage
    shared = 0.300227 - 0.250209      # A in step, B in stage
    index = 0.32 - 0.300227           # A in chunk_index, B in stage
    assert charged["step"] == pytest.approx(
        alone + b_root / 2 + shared / 2, abs=1e-6)
    assert charged["stage"] == pytest.approx(shared / 2 + index / 2,
                                             abs=1e-6)
    assert charged["chunk_index"] == pytest.approx(index / 2, abs=1e-6)
    # Gap 3: under lane A's root span alone, then under no span at all.
    under_root = 0.450231 - 0.42
    assert charged["build"] == pytest.approx(b_root / 2 + under_root,
                                             abs=1e-6)
    assert total == pytest.approx(0.08 + 0.10 + under_root, abs=1e-6)
    assert sum(charged.values()) == pytest.approx(total, abs=1e-9)


def test_what_only_structural_spans_cover_is_unspanned(recorded):
    charged, total = hostspans.charge_gaps(recorded, GAPS)
    want = 100.0 * (charged["build"] + charged["stage"]
                    + charged["step"]) / total
    assert hostspans.unspanned_pct(charged, total) == pytest.approx(want)
    assert want == pytest.approx(57.2439, abs=1e-3)
    # A gap with no build executing is not idle time of a build.
    assert hostspans.charge_gaps(recorded, [(0.46, 0.50)]) == ({}, 0.0)
    assert hostspans.unspanned_pct({}, 0.0) is None
    # Wholly inside chunk_index and lane B's stage.
    charged, total = hostspans.charge_gaps(recorded, [(0.31, 0.33)])
    assert hostspans.unspanned_pct(charged, total) == pytest.approx(50.0)


# -- the readers, on a run made by hand -----------------------------------


def _build(spans, ok=True):
    b = driver.Build(lane=0, index=0, kind="rebuild", tag="", context="",
                     storage="", context_bytes=1, exit_code=0 if ok else 1,
                     terminal={"x": 1})
    b.spans = spans
    return b


def _series(name, value, **labels):
    return (name, tuple(sorted(labels.items()))), value


BUSY = "makisu_commit_stage_busy_seconds"
MOVED = "makisu_device_transfer_bytes_total"


@pytest.fixture
def run(tmp_path):
    r = driver.Run(cell=None, seed=1, seconds=45.0, trace=True,
                   work_dir=str(tmp_path))
    r.counted = [
        _build([("memfs_sync", 1.3), ("memfs_sync.os_sync", 0.25),
                ("memfs_sync.mtime_wait", 0.75), ("tar_write", 0.5),
                ("tar_write", 1.5), ("chunk_index", 6.0),
                ("apply_layer", 0.25), ("apply_layer.inflate", 0.24)]),
        _build([("memfs_sync.os_sync", 0.75),
                ("memfs_sync.mtime_wait", 0.25), ("tar_write", 1.0),
                ("chunk_index", 2.0), ("apply_layer", 0.75)]),
        _build([("tar_write", 99.0)], ok=False),
    ]
    r.builds = list(r.counted)
    r.counters_open = dict([
        _series(BUSY, 10.0, stage="gear_readback"),
        _series(BUSY, 5.0, stage="tar_write"),
        _series(MOVED, 1e6, direction="h2d", stage="gear"),
        _series("makisu_session_dirty_paths_total", 4.0)])
    r.counters_close = dict([
        _series(BUSY, 10.6, stage="gear_readback"),
        _series(BUSY, 0.3, stage="sha_readback"),
        _series(BUSY, 1.5, stage="service_wait"),
        _series(BUSY, 50.0, stage="tar_write"),
        _series(MOVED, 31e6, direction="h2d", stage="gear"),
        _series(MOVED, 120e6, direction="h2d", stage="sha"),
        _series(MOVED, 3e6, direction="d2h", stage="gear"),
        _series("makisu_session_dirty_paths_total", 13.0)])
    return r


# Two of the three counted builds ended well: spans are summed over
# those and divided by 2; counters grow over the window and are divided
# by the 3 counted.
@pytest.mark.parametrize("metric,want", [
    ("sync_os_sync_s_per_build", (0.25 + 0.75) / 2),
    ("sync_mtime_wait_s_per_build", (0.75 + 0.25) / 2),
    ("tar_write_s_per_build", (0.5 + 1.5 + 1.0) / 2),
    ("chunk_index_s_per_build", (6.0 + 2.0) / 2),
    ("apply_layer_s_per_build", (0.25 + 0.75) / 2),
    ("device_wait_s_per_build", (0.6 + 0.3 + 1.5) / 3),
    ("device_transfer_mb_per_build", (30 + 120 + 3) / 3),
    ("session_dirty_paths_per_build", 9.0 / 3),
])
def test_reader_gives_the_hand_computed_value(run, metric, want):
    assert _reader(metric)(run) == pytest.approx(want)


def test_idle_unspanned_reader_reads_the_runs_trace(run, capsys):
    read = _reader("idle_unspanned_pct")
    assert read(run) is None                      # no device trace
    run.device_trace = xplane.DeviceTrace(
        window_s=0.5, busy_s=0.0, devices=1, ops={}, gaps=GAPS)
    assert read(run) is None                      # no trace file
    where = os.path.join(run.work_dir, "trace", "plugins", "profile", "x")
    os.makedirs(where)
    shutil.copy(TRACE, os.path.join(where, "host.xplane.pb"))
    assert read(run) == pytest.approx(57.2439, abs=1e-3)
    assert "idle seconds by innermost program span" in capsys.readouterr().out


@pytest.mark.parametrize("metric", [
    "sync_os_sync_s_per_build", "sync_mtime_wait_s_per_build",
    "tar_write_s_per_build", "chunk_index_s_per_build",
    "device_wait_s_per_build", "device_transfer_mb_per_build",
    "session_dirty_paths_per_build"])
def test_reader_returns_nothing_for_a_program_without_the_span(run, metric):
    """The parent commit has none of these spans or counters: the line
    leaves the metric out, and nothing raises."""
    for b in run.counted:
        b.spans = [("commit_layer", 2.0), ("apply_layer", 0.5)]
    old = {("makisu_device_h2d_bytes_total", (("bucket", "16384"),)): 8.0}
    run.counters_open, run.counters_close = dict(old), dict(old)
    assert _reader(metric)(run) is None


# -- PR 35: a build's service, whole ---------------------------------------

SELF = "makisu_span_self_seconds_total"


def _served(tmp_path, with_program_side=True, builds=True):
    """Two builds that ended well and one that did not; the structural
    spans' self seconds grew over the window."""
    r = driver.Run(cell=None, seed=1, seconds=45.0, trace=True,
                   work_dir=str(tmp_path))
    if builds:
        r.counted = [
            _build([("build_setup", 0.25), ("session_begin", 0.125),
                    ("wait_for_push", 0.5), ("save_manifest", 0.0625),
                    ("build", 2.0)]),
            _build([("build_setup", 0.75), ("session_begin", 0.375),
                    ("wait_for_push", 1.0), ("save_manifest", 0.03125),
                    ("build", 3.0)]),
            _build([("build_setup", 99.0), ("session_begin", 99.0),
                    ("wait_for_push", 99.0), ("save_manifest", 99.0)],
                   ok=False)]
        for b, (setup, teardown, service) in zip(r.counted, (
                (0.0625, 0.03125, 2.09375), (0.125, 0.0625, 3.1875),
                (9.0, 9.0, 99.0))):
            b.terminal = {"queue_wait_seconds": 5.0, "setup_seconds": setup,
                          "teardown_seconds": teardown,
                          "service_seconds": service}
    r.builds = list(r.counted)
    r.counters_open = dict([
        _series(SELF, 1.0, span="build"), _series(SELF, 0.5, span="stage"),
        _series("makisu_session_dirty_paths_total", 4.0)])
    r.counters_close = dict([
        _series(SELF, 1.75, span="build"), _series(SELF, 0.875, span="stage"),
        _series(SELF, 0.375, span="step"),
        _series("makisu_session_dirty_paths_total", 13.0)])
    if not with_program_side:
        for b in r.counted:
            b.spans = [("commit_layer", 2.0), ("session_finish", 0.5)]
            b.terminal = {"queue_wait_seconds": 5.0, "elapsed_seconds": 7.0}
        for counters in (r.counters_open, r.counters_close):
            for key in [k for k in counters if k[0] == SELF]:
                del counters[key]
    return r


@pytest.mark.parametrize("metric,want", [
    # Counters grow over the window and are divided by the 3 counted;
    # spans and terminal records are those of the 2 that ended well.
    ("unspanned_s_per_build", (0.75 + 0.375 + 0.375) / 3),
    ("service_s_per_build", (2.09375 + 3.1875) / 2),
    ("request_overhead_s_per_build", (0.09375 + 0.1875) / 2),
    ("build_setup_s_per_build", (0.25 + 0.75) / 2),
    ("session_begin_s_per_build", (0.125 + 0.375) / 2),
    ("wait_for_push_s_per_build", (0.5 + 1.0) / 2),
    ("save_manifest_s_per_build", (0.0625 + 0.03125) / 2),
])
def test_service_reader_reads_a_run_and_nothing_from_an_older_program(
        tmp_path, metric, want, capsys):
    read = _reader(metric)
    assert read(_served(tmp_path)) == pytest.approx(want)
    if metric == "unspanned_s_per_build":
        assert "build 0.2500  stage 0.1250  step 0.1250" \
            in capsys.readouterr().out
    # The parent's side of the driver's pair: no counter, field or span.
    assert read(_served(tmp_path, with_program_side=False)) is None
    # A window that counted no build.
    assert read(_served(tmp_path, builds=False)) is None
    if metric == "unspanned_s_per_build":
        untraced = _served(tmp_path)
        untraced.counters_open = untraced.counters_close = None
        assert read(untraced) is None


PREFETCH = "makisu_sink_prefetch_files_total"
BUSY = "makisu_commit_stage_busy_seconds"


def _prefetched(tmp_path, program_side=True, handed=True):
    """``_served``'s three counted builds, whose sinks handed files to
    their readers over the window (PR 40)."""
    r = _served(tmp_path)
    if program_side:
        r.counters_open.update([
            _series(PREFETCH, 100.0, result="ready"),
            _series(PREFETCH, 7.0, result="streamed"),
            _series(BUSY, 1.0, stage="read_wait"),
            _series(BUSY, 5.0, stage="compress_wait")])
        r.counters_close.update([
            _series(PREFETCH, 100.0 + (7_000 if handed else 0),
                    result="ready"),
            _series(PREFETCH, 1_000.0 if handed else 0.0, result="waited"),
            _series(PREFETCH, 16.0, result="streamed"),
            _series(BUSY, 1.375, stage="read_wait"),
            _series(BUSY, 50.0, stage="compress_wait")])
    return r


@pytest.mark.parametrize("metric,want", [
    # ``streamed`` files were never a reader's: 7,000 of 8,000.
    ("sink_prefetch_ready_pct", 87.5),
    ("read_wait_s_per_build", 0.375 / 3),
])
def test_prefetch_reader_reads_a_run_and_nothing_from_an_older_program(
        tmp_path, metric, want):
    read = _reader(metric)
    assert read(_prefetched(tmp_path)) == pytest.approx(want)
    # The parent's side of the driver's pair: no such series.
    assert read(_prefetched(tmp_path, program_side=False)) is None
    untraced = _prefetched(tmp_path)
    untraced.counters_open = untraced.counters_close = None
    assert read(untraced) is None
    # A window whose builds handed no file to a reader (huge-layer):
    # no share to speak of, and no second waited.
    bypassed = _prefetched(tmp_path, handed=False)
    if metric == "sink_prefetch_ready_pct":
        assert read(bypassed) is None
    else:
        assert read(bypassed) == pytest.approx(want)
        none_counted = _prefetched(tmp_path)
        none_counted.counted = []
        assert read(none_counted) is None


def test_prefetch_metrics_list_their_cells():
    import json
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json"),
              encoding="utf-8") as f:
        per_layer = json.load(f)["per_layer"]
    by_name = {m["name"]: m for m in per_layer}
    assert [m["name"] for m in per_layer[53:55]] == [
        "sink_prefetch_ready_pct", "read_wait_s_per_build"]
    # PR 41 appended four, PR 42 one, PR 45 one, PR 47 four, PR 48
    # one, PR 49 one, PR 50 four, PR 51 one, PR 52 four, PR 53 one
    assert len(per_layer) == 77
    commit = by_name["tar_write_s_per_build"]
    for name, unit, better in (("sink_prefetch_ready_pct", "%", "higher"),
                               ("read_wait_s_per_build", "s", "lower")):
        m = by_name[name]
        # Where a layer has two files for the readers: not huge-layer
        # (two files of 64 MiB), not the two churning lanes of
        # farm-unchanged.
        assert m["workloads"] == [
            "small-files-edit", "monorepo-edit", "monorepo-cold",
            "farm-churn", "farm-concurrent-churn", "multi-stage-small-edit",
            "monorepo-farm-churn"]
        assert set(m["workloads"]) < set(commit["workloads"])
        assert (m["layer"], m["moves"], m["source"]) == (
            commit["layer"], "build_p50_s", "program_counter")
        assert (m["unit"], m["better"]) == (unit, better)
        assert sorted(m) == sorted(commit)
    from makisu_tpu.utils import metrics
    assert metrics.SINK_PREFETCH_FILES_TOTAL == PREFETCH
    assert metrics.COMMIT_STAGE_BUSY == BUSY


def test_service_metrics_list_their_cells():
    import json
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json"),
              encoding="utf-8") as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    # PR 47's cell joined the lists of its pair but the five that are
    # to be retired, ``idle_unspanned_pct`` among them.
    pgzip = "huge-layer-pgzip-edit"
    # PR 50's cell likewise.
    farm = "monorepo-farm-churn"
    every = by_name["idle_unspanned_pct"]["workloads"] + [pgzip, farm]
    for name in ("unspanned_s_per_build", "service_s_per_build",
                 "request_overhead_s_per_build"):
        assert by_name[name]["workloads"] == every, name
    assert by_name["build_setup_s_per_build"]["workloads"] == [
        "farm-churn", "farm-unchanged", "monorepo-edit",
        "multi-stage-small-edit", "farm-concurrent-churn", farm]
    assert by_name["session_begin_s_per_build"]["workloads"] == [
        "farm-churn", "farm-unchanged", "monorepo-edit", "small-files-edit",
        "huge-layer-edit", "multi-stage-small-edit", "farm-concurrent-churn",
        "run-steps-edit", pgzip, farm]
    assert by_name["wait_for_push_s_per_build"]["workloads"] == [
        "monorepo-cold", "monorepo-edit", "huge-layer-edit", pgzip, farm]
    assert by_name["save_manifest_s_per_build"]["workloads"] == [
        "farm-churn", "farm-unchanged", "farm-concurrent-churn", farm]
    for name in ("unspanned_s_per_build", "service_s_per_build",
                 "request_overhead_s_per_build", "build_setup_s_per_build",
                 "session_begin_s_per_build", "wait_for_push_s_per_build",
                 "save_manifest_s_per_build"):
        assert by_name[name]["moves"] == "build_p50_s"
        assert (by_name[name]["unit"], by_name[name]["better"]) \
            == ("s", "lower")
    from makisu_tpu.utils import metrics
    assert metrics.SPAN_SELF_SECONDS == SELF


def test_every_new_metric_has_its_reader_and_its_cells():
    import json
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json"),
              encoding="utf-8") as f:
        benchmark = json.load(f)
    cells_of = {m["name"]: m["workloads"] for m in benchmark["per_layer"]}
    every = ["monorepo-cold", "farm-churn", "monorepo-edit",
             "farm-unchanged", "small-files-edit", "huge-layer-edit",
             "multi-stage-small-edit", "farm-concurrent-churn",
             "run-steps-edit"]
    assert cells_of["idle_unspanned_pct"] == every
    assert cells_of["sync_os_sync_s_per_build"] \
        == every + ["huge-layer-pgzip-edit", "monorepo-farm-churn"]
    assert cells_of["chunk_index_s_per_build"] == [
        "monorepo-cold", "monorepo-edit", "small-files-edit",
        "huge-layer-edit", "multi-stage-small-edit", "huge-layer-pgzip-edit",
        "monorepo-farm-churn"]
    for name in cells_of:
        assert os.path.exists(os.path.join(READERS, name + ".py")), name


@pytest.mark.parametrize("cell", [
    "monorepo-cold", "farm-churn", "monorepo-edit", "farm-unchanged",
    "small-files-edit", "huge-layer-edit", "multi-stage-small-edit",
    "farm-concurrent-churn", "run-steps-edit", "huge-layer-pgzip-edit",
    "monorepo-farm-churn"])
def test_every_cell_finds_its_files_and_a_reader_for_each_metric(cell):
    """What ``run.py`` looks up by name for a cell: configuration, mix,
    reference, and a reader for every metric either kind of run
    reports."""
    found = cells.Cell(os.path.join(os.path.dirname(HERE), "BENCHMARK.json"),
                       cell)
    assert callable(found.reference.cut_points)
    assert found.traffic["count"] in ("started", "completed")
    names = [m["name"] for m in found.end_to_end() + found.per_layer()]
    assert "setup_s" in names and "build_p50_s" in names
    assert len(names) == len(set(names)) > 10
    for name in names:
        assert callable(found.reader(name)), name


# -- PR 45: what a request asked about itself more than once -----------------

RESOLVED = "makisu_request_resolve_total"


def _resolved(tmp_path, program_side=True, asked=True):
    """``_served``'s run over a window of 100 requests: each parsed once
    (its locks and ``cli.main`` answered by the record), walked four
    paths and was answered twenty."""
    r = _served(tmp_path)
    if program_side:
        r.counters_open.update([
            _series(RESOLVED, 40.0, kind="parse", result="done"),
            _series(RESOLVED, 80.0, kind="parse", result="reused"),
            _series(RESOLVED, 160.0, kind="realpath", result="done"),
            _series(RESOLVED, 800.0, kind="realpath", result="reused")])
        grown = 100.0 if asked else 0.0
        r.counters_close.update([
            _series(RESOLVED, 40.0 + grown, kind="parse", result="done"),
            _series(RESOLVED, 80.0 + 2 * grown, kind="parse",
                    result="reused"),
            _series(RESOLVED, 160.0 + 4 * grown, kind="realpath",
                    result="done"),
            _series(RESOLVED, 800.0 + 20 * grown, kind="realpath",
                    result="reused")])
    return r


def test_resolve_reuse_reader_reads_a_run_and_nothing_from_an_older_program(
        tmp_path):
    read = _reader("request_resolve_reuse_pct")
    # 2,200 answered of 2,700 asked, both kinds together.
    assert read(_resolved(tmp_path)) == pytest.approx(100 * 22 / 27)
    # The parent's side of the driver's pair: no such series.
    assert read(_resolved(tmp_path, program_side=False)) is None
    untraced = _resolved(tmp_path)
    untraced.counters_open = untraced.counters_close = None
    assert read(untraced) is None
    # The series is there and did not grow: no share to give.
    assert read(_resolved(tmp_path, asked=False)) is None


def test_resolve_reuse_metric_lists_the_cells_that_report_the_request():
    import json

    from makisu_tpu.utils import metrics
    assert metrics.REQUEST_RESOLVE_TOTAL == RESOLVED
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json"),
              encoding="utf-8") as f:
        per_layer = json.load(f)["per_layer"]
    m = per_layer[60]  # appended at PR 45
    request = {x["name"]: x for x in per_layer}[
        "request_overhead_s_per_build"]
    assert m == {"name": "request_resolve_reuse_pct", "unit": "%",
                 "better": "higher", "source": "program_counter",
                 "layer": request["layer"], "moves": "build_p50_s",
                 "workloads": request["workloads"]}
    assert len(m["workloads"]) == 11  # PRs 47 and 50 appended theirs


# -- PR 48: where a new pack's bytes came from --------------------------------

PACK_SOURCE = "makisu_serve_pack_source_bytes_total"


def _packed(tmp_path, grown: dict | None):
    """``_served``'s run over a window in which new packs took
    ``grown[source]`` bytes from each source (None: a program without
    the series)."""
    r = _served(tmp_path)
    if grown is not None:
        before = {"pass": 5_000.0, "store": 700.0}
        r.counters_open.update(
            _series(PACK_SOURCE, before[source], source=source)
            for source in grown)
        r.counters_close.update(
            _series(PACK_SOURCE, before[source] + n, source=source)
            for source, n in grown.items())
    return r


@pytest.mark.parametrize("grown, want", [
    ({"pass": 64_000_000.0}, 100.0),                  # a cold build
    ({"pass": 30_000.0, "store": 10_000.0}, 75.0),
    ({"store": 8_000.0}, 0.0),                        # all read back
    ({"pass": 0.0, "store": 0.0}, None),              # no new pack
    (None, None),                                     # the parent's side
])
def test_recipe_bytes_from_pass_reader(tmp_path, grown, want):
    read = _reader("recipe_bytes_from_pass_pct")
    got = read(_packed(tmp_path, grown))
    assert got is None if want is None else got == pytest.approx(want)
    untraced = _packed(tmp_path, grown)
    untraced.counters_open = untraced.counters_close = None
    assert read(untraced) is None


def test_recipe_bytes_from_pass_is_appended_under_the_chunk_store():
    import json

    from makisu_tpu.utils import metrics
    assert metrics.SERVE_PACK_SOURCE_BYTES == PACK_SOURCE
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json"),
              encoding="utf-8") as f:
        per_layer = json.load(f)["per_layer"]
    index = {x["name"]: x for x in per_layer}["chunk_index_s_per_build"]
    assert per_layer[65] == {
        "name": "recipe_bytes_from_pass_pct", "unit": "%",
        "better": "higher", "source": "program_counter",
        "layer": index["layer"], "moves": "build_p50_s",
        "workloads": ["monorepo-cold", "monorepo-edit", "farm-churn",
                      "farm-concurrent-churn", "monorepo-farm-churn"]}
