"""Trace export + critical path: Perfetto golden, chain math, and the
`makisu-tpu report` subcommand output."""

import json

import pytest

from makisu_tpu import cli
from makisu_tpu.utils import traceexport

# A fixed two-level report: build(2.0s) -> stage(1.8s) -> {step/pull
# 1.0s, step/hash 0.6s}. Durations chosen so the critical path is
# build -> stage -> step[pull] and self-times are non-trivial.
REPORT = {
    "schema": "makisu-tpu.metrics.v1",
    "trace_id": "0af7651916cd43dd8448eb211c80319c",
    "command": "build",
    "exit_code": 0,
    "spans": [{
        "name": "build",
        "span_id": "b7ad6b7169203331",
        "start": 1000.0,
        "duration": 2.0,
        "children": [{
            "name": "stage",
            "span_id": "00f067aa0ba902b7",
            "parent_id": "b7ad6b7169203331",
            "start": 1000.1,
            "duration": 1.8,
            "attrs": {"alias": "0"},
            "children": [
                {"name": "pull_cache_layers",
                 "span_id": "1111111111111111",
                 "parent_id": "00f067aa0ba902b7",
                 "start": 1000.2, "duration": 1.0},
                {"name": "commit_layer",
                 "span_id": "2222222222222222",
                 "parent_id": "00f067aa0ba902b7",
                 "start": 1001.2, "duration": 0.6,
                 "error": "boom"},
            ],
        }],
    }],
    "counters": {
        "makisu_cache_pull_total": [
            {"labels": {"result": "hit"}, "value": 3.0},
            {"labels": {"result": "miss"}, "value": 1.0},
        ],
        "makisu_bytes_hashed_total": [
            {"labels": {"backend": "native"}, "value": 4096.0},
            {"labels": {"backend": "pallas"}, "value": 1048576.0},
        ],
    },
    "gauges": {},
    "histograms": {},
}

PERFETTO_GOLDEN = {
    "traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 1,
         "args": {"name": "makisu-tpu build"}},
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
         "args": {"name": "build"}},
        {"name": "build", "ph": "X", "ts": 1000000000.0,
         "dur": 2000000.0, "pid": 1, "tid": 1, "cat": "other",
         "args": {"span_id": "b7ad6b7169203331"}},
        {"name": "stage", "ph": "X", "ts": 1000100000.0,
         "dur": 1800000.0, "pid": 1, "tid": 1, "cat": "other",
         "args": {"span_id": "00f067aa0ba902b7",
                  "parent_id": "b7ad6b7169203331", "alias": "0"}},
        {"name": "pull_cache_layers", "ph": "X", "ts": 1000200000.0,
         "dur": 1000000.0, "pid": 1, "tid": 1, "cat": "pull",
         "args": {"span_id": "1111111111111111",
                  "parent_id": "00f067aa0ba902b7"}},
        {"name": "commit_layer", "ph": "X", "ts": 1001200000.0,
         "dur": 600000.0, "pid": 1, "tid": 1, "cat": "hash",
         "args": {"span_id": "2222222222222222",
                  "parent_id": "00f067aa0ba902b7",
                  "error": "boom"}},
    ],
    "displayTimeUnit": "ms",
    "otherData": {"trace_id": "0af7651916cd43dd8448eb211c80319c"},
}


def test_perfetto_trace_golden():
    assert traceexport.perfetto_trace(REPORT) == PERFETTO_GOLDEN


def test_perfetto_trace_is_json_serializable():
    json.dumps(traceexport.perfetto_trace(REPORT))


def test_perfetto_trace_tolerates_open_span():
    torn = {"spans": [{"name": "build", "start": 1.0,
                       "duration": None}]}
    [_, _, event] = traceexport.perfetto_trace(torn)["traceEvents"]
    assert event["dur"] == 0.0


@pytest.mark.parametrize("name,phase", [
    ("pull_cache_layers", "pull"),
    ("from", "pull"),
    ("chunk_fetch", "chunk"),
    ("hash_batch", "hash"),
    ("commit_layer", "hash"),
    ("registry_push", "push"),
    ("stage", "other"),
])
def test_phase_classification(name, phase):
    assert traceexport.phase_of(name) == phase


def test_critical_path_descends_longest_child():
    path = traceexport.critical_path(REPORT)
    assert [hop["name"] for hop in path] == \
        ["build", "stage", "pull_cache_layers"]
    # First hop IS the root, so the path total IS the root wall time.
    assert path[0]["duration"] == 2.0
    assert path[0]["self"] == pytest.approx(0.2)  # 2.0 - 1.8
    assert path[1]["self"] == pytest.approx(0.2)  # 1.8 - 1.6
    assert path[2]["self"] == pytest.approx(1.0)  # leaf


def test_self_time_reconstructs_wall_time():
    total = sum(traceexport.self_time_by_name(REPORT).values())
    assert total == pytest.approx(2.0)


def test_self_time_is_the_recorded_one_where_a_span_carries_it():
    """A child on a pool thread overlaps its parent: the program does
    not subtract it (``Span.self_seconds``), and neither does anything
    that reads the report, so the tree and the phases agree."""
    report = {"spans": [{
        "name": "build", "start": 0.0, "duration": 2.0,
        "self_seconds": 0.5,
        "children": [
            {"name": "commit_layer", "start": 0.5, "duration": 1.5,
             "self_seconds": 1.5,       # its one child ran beside it
             "children": [{"name": "registry_push", "start": 0.6,
                           "duration": 1.2}]},
        ]}]}
    assert traceexport.self_time_by_name(report) == {
        "build": 0.5, "commit_layer": 1.5, "registry_push": 1.2}
    assert [hop["self"] for hop in traceexport.critical_path(report)] \
        == [0.5, 1.5, 1.2]
    assert traceexport.phase_totals(report)["hash"] == 1.5


def test_phase_totals():
    phases = traceexport.phase_totals(REPORT)
    assert phases["pull"] == pytest.approx(1.0)
    assert phases["hash"] == pytest.approx(0.6)
    assert phases["other"] == pytest.approx(0.4)
    assert phases["push"] == 0.0


def test_cache_and_hash_counters():
    cache = traceexport.cache_stats(REPORT)
    assert cache["hit"] == 3.0 and cache["miss"] == 1.0
    assert cache["ratio"] == pytest.approx(0.75)
    hashed = traceexport.bytes_hashed_by_backend(REPORT)
    assert hashed == {"native": 4096.0, "pallas": 1048576.0}


def test_render_report_text():
    text = traceexport.render_report(REPORT, event_log=[
        {"ts": 1, "type": "span_start"},
        {"ts": 2, "type": "span_end"},
        {"ts": 3, "type": "cache"},
    ])
    assert "trace id: 0af7651916cd43dd8448eb211c80319c" in text
    assert "wall time: 2.000s" in text
    assert "critical path (longest span chain, total 2.000s):" in text
    assert "pull_cache_layers" in text
    assert "hit ratio 75.0%" in text
    assert "pallas=1.0MiB" in text
    assert "event log: 3 events" in text
    assert "cache=1" in text


def test_render_report_empty_spans():
    text = traceexport.render_report(
        {"schema": "makisu-tpu.metrics.v1", "spans": []})
    assert "no spans recorded" in text


# -- the CLI subcommand ----------------------------------------------------


def test_cli_report_subcommand(tmp_path, capsys):
    metrics_file = tmp_path / "report.json"
    metrics_file.write_text(json.dumps(REPORT))
    events_file = tmp_path / "events.jsonl"
    events_file.write_text('{"ts": 1, "type": "build_start"}\n'
                           '{"ts": 2, "type": "build_end"}\n')
    code = cli.main(["report", str(metrics_file),
                     "--events", str(events_file)])
    assert code == 0
    out = capsys.readouterr().out
    assert "critical path" in out
    assert "event log: 2 events" in out
    # Acceptance: the printed critical-path total equals the root
    # span's wall time (within 5%; here exactly).
    assert "total 2.000s" in out


def test_cli_report_rejects_foreign_json(tmp_path):
    bogus = tmp_path / "other.json"
    bogus.write_text('{"hello": "world"}')
    with pytest.raises(SystemExit, match="not a makisu-tpu metrics"):
        cli.main(["report", str(bogus)])


def test_cli_report_salvages_torn_event_log(tmp_path, capsys):
    """A build killed mid-write leaves a torn final event line; the
    report must analyze the valid prefix, not die."""
    metrics_file = tmp_path / "report.json"
    metrics_file.write_text(json.dumps(REPORT))
    torn = tmp_path / "torn.jsonl"
    torn.write_text('{"ts": 1, "type": "build_start"}\n{"ts": 2, "ty')
    code = cli.main(["report", str(metrics_file),
                     "--events", str(torn)])
    assert code == 0
    assert "event log: 1 events" in capsys.readouterr().out
