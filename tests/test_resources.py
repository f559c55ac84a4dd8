"""Resource sampler: /proc readings, gauge publication, span
attribution, and the report's per-phase resource section."""

import time

import pytest

from makisu_tpu import native
from makisu_tpu.utils import metrics, resources, traceexport


def test_read_sample_shape():
    sample = resources.read_sample()
    assert sample["rss_bytes"] > 0
    assert sample["cpu_seconds"] > 0
    assert sample["threads"] >= 1
    # Linux CI/dev hosts have procfs; these fields must be present
    # there (they degrade away only on exotic hosts).
    assert sample.get("open_fds", 1) >= 1


def test_sampler_publishes_gauges_and_trajectory():
    sampler = resources.ResourceSampler(interval=60)  # manual ticks
    sampler.sample_once()
    sampler.sample_once()
    assert len(sampler.trajectory()) == 2
    g = metrics.global_registry()
    assert g.gauge_value("makisu_process_rss_bytes") > 0
    assert g.gauge_value("makisu_process_cpu_seconds") > 0
    assert g.gauge_value("makisu_process_threads") >= 1


def _burn_under_spans(sampler):
    """Two nested spans on this thread, CPU burned under the inner one
    between the sampler's beats; returns (outer, inner)."""
    registry = metrics.MetricsRegistry()
    token = metrics.set_build_registry(registry)
    try:
        with metrics.span("push_layers") as outer:
            with metrics.span("hash_batch") as inner:
                sampler.sample_once()
                sampler.thread_states.beat()   # this thread is watched
                time.sleep(0.05)               # the reader's baseline
                sampler.thread_states.beat()
                t0 = time.thread_time()
                while time.thread_time() - t0 < 0.1:
                    sum(i * i for i in range(10_000))
                time.sleep(0.03)               # the reader's next beat
                sampler.thread_states.beat()
                sampler.sample_once()
    finally:
        metrics.reset_build_registry(token)
        sampler.thread_states.release()
    return outer, inner


def test_samples_attribute_to_open_spans():
    """Open spans record peak RSS; the CPU seconds a thread ran are
    charged to its innermost open span, from the scheduler's own clock
    for that thread. Closed spans carry the result in to_dict()."""
    if native.thread_state_reader() is None:
        pytest.skip("libthreadstate.so cannot be built or loaded here")
    resources.stop()  # the process singleton must not race the asserts
    sampler = resources.ResourceSampler(interval=60)
    outer, inner = _burn_under_spans(sampler)
    for span in (outer, inner):
        d = span.to_dict()
        assert d["resources"]["peak_rss_bytes"] > 0
    # The innermost span got this thread's CPU, not the parent.
    assert inner.to_dict()["resources"]["cpu_seconds"] > 0.05
    assert outer.to_dict()["resources"]["cpu_seconds"] == 0


def test_cpu_is_left_out_where_nothing_can_look():
    """No reader (the library cannot be built or loaded): a span's
    resources keep the peak RSS and say nothing of CPU, rather than a
    share of the process's."""
    resources.stop()
    sampler = resources.ResourceSampler(interval=60)
    sampler.thread_states._resolved = True  # resolved to no reader
    outer, inner = _burn_under_spans(sampler)
    for span in (outer, inner):
        assert span.to_dict()["resources"] == {
            "peak_rss_bytes": span.peak_rss}


def test_span_without_sampling_has_no_resources():
    with metrics.span("quick") as s:
        pass
    assert "resources" not in s.to_dict()


def test_report_renders_resources_by_phase():
    report = {
        "schema": "makisu-tpu.metrics.v1",
        "spans": [{
            "name": "build", "span_id": "aa", "start": 100.0,
            "duration": 2.0,
            "resources": {"peak_rss_bytes": 64 << 20,
                          "cpu_seconds": 0.5},
            "children": [{
                "name": "push_layers", "span_id": "bb",
                "start": 100.5, "duration": 1.0,
                "resources": {"peak_rss_bytes": 128 << 20,
                              "cpu_seconds": 0.25},
            }],
        }],
    }
    by_phase = traceexport.resources_by_phase(report)
    assert by_phase["push"]["peak_rss_bytes"] == 128 << 20
    assert by_phase["other"]["cpu_seconds"] == 0.5
    # A span with no CPU on record (no reader) adds none.
    del report["spans"][0]["resources"]["cpu_seconds"]
    assert traceexport.resources_by_phase(report)["other"] == {
        "peak_rss_bytes": 64 << 20, "cpu_seconds": 0.0}
    text = traceexport.render_report(report)
    assert "resource usage by phase" in text
    assert "128.0MiB" in text


def test_ensure_started_is_idempotent():
    first = resources.ensure_started(interval=30)
    second = resources.ensure_started(interval=1)
    assert first is second
    resources.stop()
