"""Telemetry tests: registry semantics, Prometheus rendering, span
trees, and the worker-grade isolation guarantee (two concurrent builds
must each see only their own telemetry, mirroring the build-sink log
isolation)."""

import json
import threading

import pytest

from makisu_tpu.utils import metrics
from makisu_tpu.worker import WorkerClient, WorkerServer


# -- registry semantics ----------------------------------------------------


def test_counter_add_and_totals():
    reg = metrics.MetricsRegistry()
    reg.counter_add("hits", 1, result="hit")
    reg.counter_add("hits", 2, result="hit")
    reg.counter_add("hits", 5, result="miss")
    assert reg.counter_total("hits") == 8
    assert reg.counter_total("hits", result="hit") == 3
    assert reg.counter_total("hits", result="miss") == 5
    assert reg.counter_total("absent") == 0
    assert reg.counter_by_label("hits", "result") == {
        "hit": 3.0, "miss": 5.0}


def test_gauge_last_write_wins():
    reg = metrics.MetricsRegistry()
    reg.gauge_set("depth", 3)
    reg.gauge_set("depth", 7)
    assert reg.report()["gauges"]["depth"] == [
        {"labels": {}, "value": 7.0}]


def test_histogram_tracks_count_sum_min_max():
    reg = metrics.MetricsRegistry()
    for v in (0.5, 1.5, 4.0):
        reg.observe("lat", v)
    [series] = reg.report()["histograms"]["lat"]
    assert series["count"] == 3
    assert series["sum"] == 6.0
    assert series["min"] == 0.5
    assert series["max"] == 4.0


def test_span_tree_nesting_and_error():
    reg = metrics.MetricsRegistry()
    token = metrics.set_build_registry(reg)
    try:
        with metrics.span("outer", alias="0"):
            with metrics.span("inner"):
                pass
        with pytest.raises(ValueError):
            with metrics.span("failing"):
                raise ValueError("boom")
    finally:
        metrics.reset_build_registry(token)
    spans = reg.report()["spans"]
    assert [s["name"] for s in spans] == ["outer", "failing"]
    assert spans[0]["attrs"] == {"alias": "0"}
    assert [c["name"] for c in spans[0].get("children", [])] == ["inner"]
    assert spans[0]["duration"] is not None
    assert "ValueError: boom" in spans[1]["error"]


def test_writes_land_in_both_scopes():
    reg = metrics.MetricsRegistry()
    token = metrics.set_build_registry(reg)
    try:
        metrics.counter_add("test_dual_scope_total", 2)
    finally:
        metrics.reset_build_registry(token)
    assert reg.counter_total("test_dual_scope_total") == 2
    assert metrics.global_registry().counter_total(
        "test_dual_scope_total") >= 2


def test_concurrent_contexts_isolated():
    """Two threads with their own bound registries: counters and spans
    never cross (the contextvar scoping the worker relies on)."""
    regs = {}
    barrier = threading.Barrier(2)

    def one(i):
        reg = metrics.MetricsRegistry()
        regs[i] = reg
        token = metrics.set_build_registry(reg)
        try:
            barrier.wait(timeout=5)
            with metrics.span(f"build-{i}"):
                metrics.counter_add("test_iso_total", i + 1, who=str(i))
        finally:
            metrics.reset_build_registry(token)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(2):
        report = regs[i].report()
        assert [s["name"] for s in report["spans"]] == [f"build-{i}"]
        assert regs[i].counter_total("test_iso_total") == i + 1
        assert regs[i].counter_total("test_iso_total",
                                     who=str(1 - i)) == 0


def test_spawned_thread_inherits_context():
    """Threads started via contextvars.copy_context (async cache
    pushes, chunk uploads) report into the spawning build's registry."""
    import contextvars

    reg = metrics.MetricsRegistry()
    token = metrics.set_build_registry(reg)
    try:
        t = threading.Thread(
            target=contextvars.copy_context().run,
            args=(lambda: metrics.counter_add("test_inherit_total"),))
        t.start()
        t.join()
    finally:
        metrics.reset_build_registry(token)
    assert reg.counter_total("test_inherit_total") == 1


# -- self time ---------------------------------------------------------------


@pytest.fixture
def clocked(monkeypatch):
    """A registry bound to this context, a clock the test moves by hand
    under ``metrics.span``, and the ``span_end`` events by name."""
    import types

    from makisu_tpu.utils import events
    clock = types.SimpleNamespace(now=100.0, cpu=10.0)
    monkeypatch.setattr(metrics, "time", types.SimpleNamespace(
        monotonic=lambda: clock.now, time=lambda: 1e9 + clock.now,
        thread_time=lambda: clock.cpu))
    reg = metrics.MetricsRegistry()
    token = metrics.set_build_registry(reg)
    ended = {}
    sink = events.add_sink(
        lambda e: ended.update({e["name"]: e})
        if e["type"] == "span_end" else None)
    yield clock, reg, ended
    events.reset_sink(sink)
    metrics.reset_build_registry(token)


def _two_children(clock):
    clock.now += 1.0
    with metrics.span("a"):
        clock.now += 2.0
    clock.now += 0.5
    with metrics.span("b"):
        clock.now += 3.0
    clock.now += 0.25
    return 1.75


def _child_on_a_pool_thread(clock):
    from concurrent.futures import ThreadPoolExecutor

    from makisu_tpu.utils import concurrency

    def pooled():
        with metrics.span("pooled"):
            clock.now += 4.0

    clock.now += 1.0
    with ThreadPoolExecutor(1) as pool:
        concurrency.submit_ctx(pool, pooled).result()
    clock.now += 1.0
    # It ran beside its parent, under a copied context: not subtracted.
    return 6.0


def _child_that_raises(clock):
    clock.now += 1.0
    with pytest.raises(ValueError):
        with metrics.span("failing"):
            clock.now += 2.0
            raise ValueError("boom")
    clock.now += 1.0
    return 2.0


def _leaf(clock):
    clock.now += 3.0
    return None


@pytest.mark.parametrize("body", [
    _two_children, _child_on_a_pool_thread, _child_that_raises, _leaf],
    ids=lambda f: f.__name__.strip("_"))
def test_self_time_is_duration_less_the_children_on_the_spans_thread(
        clocked, body):
    clock, reg, ended = clocked
    with metrics.span("parent"):
        want = body(clock)
    [parent] = reg.report()["spans"]
    assert parent["duration"] == pytest.approx(clock.now - 100.0)
    if want is None:
        # A leaf's self time is its duration: nothing says it twice.
        assert "self_seconds" not in parent
        assert "self_seconds" not in ended["parent"]
        return
    assert parent["self_seconds"] == pytest.approx(want)
    assert ended["parent"]["self_seconds"] == pytest.approx(want)
    for child in parent["children"]:
        assert "self_seconds" not in child
        assert child["parent_id"] == parent["span_id"]


def test_self_seconds_counter_grows_for_the_structural_spans_alone(clocked):
    """The opener says which span is structural; the name says nothing."""
    clock, reg, _ = clocked
    in_process = metrics.global_registry().counter_by_label(
        metrics.SPAN_SELF_SECONDS, "span")
    with metrics.span("build", structural=True):
        clock.now += 0.5
        clock.cpu += 0.25
        with metrics.span("context_scan"):
            clock.now += 1.0
            clock.cpu += 0.5
            with metrics.span("copy_checksum"):
                clock.now += 1.0
                clock.cpu += 1.0
            with metrics.span("step"):          # a namesake, not marked
                clock.now += 8.0
        with metrics.span("stage", structural=True):
            clock.now += 0.25
            with metrics.span("step", structural=True):     # a leaf
                clock.now += 0.125
                clock.cpu += 0.125
            with metrics.span("step", structural=True):
                clock.now += 0.0625
                clock.cpu += 0.03125
                with metrics.span("commit_layer"):
                    clock.now += 2.0
                    clock.cpu += 0.5
    want = {"build": 0.5, "stage": 0.25, "step": 0.125 + 0.0625}
    assert reg.counter_by_label(metrics.SPAN_SELF_SECONDS, "span") \
        == pytest.approx(want)
    # The same on the thread's CPU clock: what a child burned, however
    # deep, is the child's; `stage` waited all its own quarter second.
    assert reg.counter_by_label(metrics.SPAN_SELF_CPU_SECONDS, "span") \
        == pytest.approx({"build": 0.25, "stage": 0.0,
                          "step": 0.125 + 0.03125})
    # The mark is the opener's alone: it is no attribute of the span.
    [root] = reg.report()["spans"]
    assert "attrs" not in root
    # The worker's /metrics serves the process's sum of the same.
    after = metrics.global_registry().counter_by_label(
        metrics.SPAN_SELF_SECONDS, "span")
    # (Other commands' root spans, from tests that ran before this one
    # in the process, are in it too, and did not grow.)
    grown = {k: after[k] - in_process.get(k, 0.0) for k in after}
    assert {k: v for k, v in grown.items() if v} == pytest.approx(want)
    assert 'makisu_span_self_seconds_total{span="stage"}' \
        in metrics.render_prometheus()


# -- Prometheus text format ------------------------------------------------


def test_prometheus_golden():
    reg = metrics.MetricsRegistry()
    reg.counter_add("makisu_cache_pull_total", 3, result="hit")
    reg.counter_add("makisu_cache_pull_total", 1, result="miss")
    reg.counter_add("makisu_bytes_hashed_total", 4096,
                    backend="python", path="layer_sink")
    reg.gauge_set("makisu_cache_push_queue_depth", 2)
    reg.observe("makisu_step_seconds", 0.25, buckets=(0.1, 1.0))
    expected = (
        '# TYPE makisu_bytes_hashed_total counter\n'
        'makisu_bytes_hashed_total{backend="python",path="layer_sink"}'
        ' 4096\n'
        '# TYPE makisu_cache_pull_total counter\n'
        'makisu_cache_pull_total{result="hit"} 3\n'
        'makisu_cache_pull_total{result="miss"} 1\n'
        '# TYPE makisu_cache_push_queue_depth gauge\n'
        'makisu_cache_push_queue_depth 2\n'
        '# TYPE makisu_step_seconds histogram\n'
        'makisu_step_seconds_bucket{le="0.1"} 0\n'
        'makisu_step_seconds_bucket{le="1"} 1\n'
        'makisu_step_seconds_bucket{le="+Inf"} 1\n'
        'makisu_step_seconds_sum 0.25\n'
        'makisu_step_seconds_count 1\n'
    )
    assert metrics.render_prometheus(reg) == expected


def test_prometheus_histogram_buckets_cumulative():
    """Multiple observations landing in one bucket must render as a
    monotonic cumulative ladder capped by _count (regression: buckets
    were double-cumulated, inflating every le above the value)."""
    reg = metrics.MetricsRegistry()
    reg.observe("lat", 0.002)
    reg.observe("lat", 0.002)
    reg.observe("lat", 0.3)
    out = metrics.render_prometheus(reg)
    assert 'lat_bucket{le="0.005"} 2' in out
    assert 'lat_bucket{le="0.01"} 2' in out
    assert 'lat_bucket{le="0.5"} 3' in out
    assert 'lat_bucket{le="60"} 3' in out
    assert 'lat_bucket{le="+Inf"} 3' in out
    assert 'lat_count 3' in out


def test_prometheus_label_escaping():
    reg = metrics.MetricsRegistry()
    reg.counter_add("weird_total", 1, msg='say "hi"\nback\\slash')
    out = metrics.render_prometheus(reg)
    assert r'msg="say \"hi\"\nback\\slash"' in out


# -- worker integration ----------------------------------------------------


@pytest.fixture
def worker(tmp_path):
    server = WorkerServer(str(tmp_path / "worker.sock"))
    thread = server.serve_background()
    yield server
    server.shutdown()
    server.server_close()
    thread.join(timeout=5)


def _build_args(tmp_path, i, dockerfile, files):
    ctx = tmp_path / f"mctx{i}"
    ctx.mkdir()
    (ctx / "Dockerfile").write_text(dockerfile)
    for name, content in files.items():
        (ctx / name).write_text(content)
    (tmp_path / f"mroot{i}").mkdir()
    return [
        "--metrics-out", str(tmp_path / f"report{i}.json"),
        "build", str(ctx), "-t", f"w/metrics{i}:1",
        "--storage", str(tmp_path / f"mstore{i}"),
        "--root", str(tmp_path / f"mroot{i}"),
    ]


def _step_spans(span):
    out = [span] if span["name"] == "step" else []
    for child in span.get("children", []):
        out.extend(_step_spans(child))
    return out


def test_worker_metrics_endpoint_serves_prometheus(tmp_path, worker):
    client = WorkerClient(worker.socket_path)
    code = client.build(_build_args(
        tmp_path, 0, "FROM scratch\nCOPY data.txt /data.txt\n",
        {"data.txt": "payload"}))
    assert code == 0
    text = client.metrics()
    assert "# TYPE makisu_layer_commits_total counter" in text
    assert "# TYPE makisu_bytes_hashed_total counter" in text
    # First build on a fresh store: the cache prefetch misses.
    assert 'makisu_cache_pull_total{result="miss"}' in text
    assert "# TYPE makisu_worker_builds_total counter" in text


def test_worker_build_response_carries_exit_and_elapsed(tmp_path, worker):
    client = WorkerClient(worker.socket_path)
    code = client.build(_build_args(
        tmp_path, 1, "FROM scratch\nCOPY data.txt /data.txt\n",
        {"data.txt": "payload"}))
    assert code == 0
    assert client.last_build["exit_code"] == 0
    assert client.last_build["elapsed_seconds"] >= 0


def test_concurrent_builds_have_isolated_telemetry(tmp_path, worker):
    """Two concurrent /build requests: each --metrics-out report holds
    only its own span tree and counters — build A (two COPY steps, two
    layer commits) and build B (one of each) must not bleed."""
    results = {}

    def one(i, dockerfile, files):
        client = WorkerClient(worker.socket_path)
        results[i] = client.build(_build_args(tmp_path, 10 + i,
                                              dockerfile, files))

    threads = [
        threading.Thread(target=one, args=(
            0, "FROM scratch\nCOPY a.txt /a.txt\nCOPY b.txt /b.txt\n",
            {"a.txt": "aaa", "b.txt": "bbb"})),
        threading.Thread(target=one, args=(
            1, "FROM scratch\nCOPY c.txt /c.txt\n", {"c.txt": "ccc"})),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == {0: 0, 1: 0}
    reports = [json.loads((tmp_path / f"report{10 + i}.json").read_text())
               for i in range(2)]
    step_counts = []
    for report in reports:
        steps = [s for top in report["spans"]
                 for s in _step_spans(top)]
        step_counts.append(len(steps))
    # A: FROM + COPY + COPY = 3 steps; B: FROM + COPY = 2 steps.
    assert step_counts == [3, 2]

    def commits(report):
        return sum(s["value"] for s in report["counters"].get(
            "makisu_layer_commits_total", []))

    assert commits(reports[0]) == 2
    assert commits(reports[1]) == 1


def test_write_report_atomic_with_extras(tmp_path):
    """write_report lands complete JSON (tmp + os.replace) including
    caller extras, and stringifies non-JSON-native span attrs instead
    of failing the invocation."""
    import os

    reg = metrics.MetricsRegistry()
    token = metrics.set_build_registry(reg)
    try:
        with metrics.span("build", where=tmp_path):  # Path attr
            metrics.counter_add("makisu_layer_commits_total")
    finally:
        metrics.reset_build_registry(token)
    out = tmp_path / "report.json"
    metrics.write_report(str(out), reg, command="build", exit_code=0)
    report = json.loads(out.read_text())
    assert report["command"] == "build"
    assert report["exit_code"] == 0
    assert report["spans"][0]["attrs"]["where"] == str(tmp_path)
    assert not [p for p in os.listdir(tmp_path)
                if p.startswith("report.json.tmp.")]
