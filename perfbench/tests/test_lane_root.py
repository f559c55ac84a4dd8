"""One ``--root`` a lane (PR 54): ``traffic["root"]`` under a client of
the test's own, the two mixes against their twins, the staged entries
laid over ``BENCHMARK.json``, and the four readers of the session's
counters over canned expositions.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q

No chip, no worker and no build: a second."""

import copy
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(PERFBENCH)
sys.path.insert(0, PERFBENCH)
sys.path.insert(1, CHECKOUT)

from pbharness import cells, driver, stats  # noqa: E402

run_staged = cells._load_module(
    os.path.join(PERFBENCH, "staged", "run_staged.py"))
TWINS = {"monorepo-edit-resident": "monorepo-edit",
         "farm-unchanged-resident": "farm-unchanged"}
MIXES = {"edit-resident": "edit", "unchanged-resident": "unchanged"}
NEW_READERS = ("session_hits_per_build", "session_invalidations_per_build",
               "session_restores_per_build", "layer_memo_replays_per_build")
FLAGS = ["--hasher", "tpu", "--commit", "explicit"]


def _load(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


class FakeClient:
    """Records each ``argv`` and whether its ``--root`` stood when the
    build was asked for; builds nothing."""

    def __init__(self):
        self.argvs, self.root_stood = [], []
        self.last_build, self.last_events = {"ok": True}, []

    def build(self, argv):
        self.argvs.append(list(argv))
        self.root_stood.append(os.path.isdir(argv[argv.index("--root") + 1]))
        return 0


def _lane(tmp_path, traffic):
    cell = types.SimpleNamespace(
        config={"lanes": 1, "context": {}, "build_flags": list(FLAGS)},
        traffic=dict({"count": "started"}, **traffic))
    run = driver.Run(cell=cell, seed=3, seconds=1.0, trace=False,
                     work_dir=str(tmp_path))
    lane = driver._Lane(run, 0, str(tmp_path / "worker.sock"))
    lane.client = FakeClient()
    os.makedirs(lane.contexts[0])
    return lane


def _roots(lane):
    return [argv[argv.index("--root") + 1] for argv in lane.client.argvs]


@pytest.mark.parametrize("traffic", [{}, {"root": "build"}],
                         ids=["no key", "build"])
def test_a_root_of_its_own_each_build_as_the_parent_made_it(tmp_path,
                                                            traffic):
    lane = _lane(tmp_path, traffic)
    for k in range(5):
        lane.build("cold" if k == 0 else "rebuild")
        # Made before its build, gone after it; nothing else is left.
        assert lane.client.root_stood[k]
        assert sorted(os.listdir(lane.dir)) == ["ctx0"]
    d = str(tmp_path / "lane0")
    # The parent's ``_Lane.build`` (e596bff), letter for letter.
    assert lane.client.argvs == [
        ["--log-level", "error", "build", f"{d}/ctx0", "-t",
         f"perfbench/lane0:b{k}", "--storage", f"{d}/storage", "--root",
         f"{d}/root{k}", "--hasher", "tpu", "--commit", "explicit"]
        for k in range(5)]


def test_one_root_for_the_lanes_whole_run(tmp_path):
    lane = _lane(tmp_path, {"root": "lane"})
    for k in range(5):
        lane.build("cold" if k == 0 else "rebuild")
        assert os.path.isdir(os.path.join(lane.dir, "root"))
    assert set(_roots(lane)) == {os.path.join(lane.dir, "root")}
    assert all(lane.client.root_stood)
    assert os.path.dirname(_roots(lane)[0]) == lane.dir
    # But for --root the argv is the other mode's.
    d = str(tmp_path / "lane0")
    assert lane.client.argvs == [
        ["--log-level", "error", "build", f"{d}/ctx0", "-t",
         f"perfbench/lane0:b{k}", "--storage", f"{d}/storage", "--root",
         f"{d}/root", "--hasher", "tpu", "--commit", "explicit"]
        for k in range(5)]


@pytest.mark.parametrize("value", ["run", "", None, "Lane", 1])
def test_any_other_value_raises_before_a_lane_exists(tmp_path, value):
    with pytest.raises(ValueError, match="traffic.root"):
        _lane(tmp_path, {"root": value})
    assert not os.path.exists(tmp_path / "lane0")


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_a_resident_mix_is_its_twin_but_for_the_root(mix):
    ours = _load(PERFBENCH, "traffic", mix + ".json")
    twin = _load(PERFBENCH, "traffic", MIXES[mix] + ".json")
    assert ours["name"] == mix and ours["root"] == "lane"
    assert "root" not in twin
    assert ours["what"] != twin["what"]
    for d in (ours, twin):
        for key in ("name", "what", "root"):
            d.pop(key, None)
    assert ours == twin


@pytest.mark.parametrize("mix", ["churn", "cold", "edit", "unchanged"])
def test_the_mixes_that_were_there_have_no_root_key(mix):
    assert "root" not in _load(PERFBENCH, "traffic", mix + ".json")


@pytest.fixture(scope="module")
def composed():
    return run_staged.composed()


@pytest.mark.parametrize("cell", sorted(TWINS))
def test_a_staged_cell_joins_every_list_of_its_twin(composed, cell):
    twin = TWINS[cell]
    [ours] = [w for w in composed["workloads"] if w["name"] == cell]
    [theirs] = [w for w in composed["workloads"] if w["name"] == twin]
    assert (ours["config"], ours["chips"]) == (theirs["config"], 1)
    assert MIXES[ours["traffic"]] == theirs["traffic"]
    assert len(ours["why"]) <= 200
    listed = 0
    for metric in composed["end_to_end"] + composed["per_layer"]:
        if "workloads" in metric:
            assert (cell in metric["workloads"]) \
                == (twin in metric["workloads"]), metric["name"]
            listed += cell in metric["workloads"]
    # The twin's lists (49 and 36 per-layer, one end-to-end) and the four.
    assert listed == {"monorepo-edit": 49, "farm-unchanged": 36}[twin] + 5
    found = cells.Cell.__new__(cells.Cell)
    found.root, found.benchmark = CHECKOUT, composed
    for name in NEW_READERS:
        assert callable(found.reader(name))


def test_composition_leaves_the_benchmark_as_it_is_but_for_what_is_staged(
        composed):
    benchmark = _load(CHECKOUT, "BENCHMARK.json")
    staged = _load(PERFBENCH, "staged", "resident.json")
    n = len(benchmark["workloads"])
    assert composed["workloads"][:n] == benchmark["workloads"]
    assert [w["name"] for w in composed["workloads"][n:]] == sorted(
        TWINS, reverse=True)
    m = len(benchmark["per_layer"])
    assert [x["name"] for x in composed["per_layer"][m:]] == list(NEW_READERS)
    for theirs, ours in zip(
            benchmark["end_to_end"] + benchmark["per_layer"],
            composed["end_to_end"] + composed["per_layer"]):
        ours = copy.deepcopy(ours)
        if "workloads" in ours:
            ours["workloads"] = [w for w in ours["workloads"]
                                 if w not in TWINS]
        assert ours == theirs
    for key in ("command", "run_seconds"):
        assert composed[key] == benchmark[key]
    for entry in staged["per_layer"]:
        assert entry["layer"] == "resident session (worker/session.py)"
        assert (entry["moves"], entry["source"], entry["unit"]) \
            == ("build_p50_s", "program_counter", "1/build")
        assert entry["workloads"] == sorted(TWINS.values(), reverse=True) \
            + sorted(TWINS, reverse=True)
    # Laid over itself it adds nothing: the day the entries move.
    assert run_staged.compose(composed, staged) == composed


def _exposition(scale):
    return f"""\
# TYPE makisu_session_hits counter
makisu_session_hits {3 * scale}
makisu_session_dirty_paths_total {5 * scale}
makisu_session_invalidations_total{{reason="lru"}} {4 * scale}
makisu_session_invalidations_total{{reason="lru_restore"}} {2 * scale}
makisu_session_snapshot_restores_total{{result="ok"}} {6 * scale}
makisu_session_snapshot_restores_total{{reason="stale",result="refused"}} {scale}
makisu_layer_replay_total{{result="memo"}} {8 * scale}
makisu_layer_replay_total{{result="inflate"}} {scale}
"""


SESSIONLESS = """\
makisu_session_dirty_paths_total 0
makisu_layer_replay_total{result="unread"} 7
"""


def _record(open_text, close_text, counted=2):
    parse = stats.parse_prometheus
    return types.SimpleNamespace(
        counted=[object()] * counted,
        counters_open=None if open_text is None else parse(open_text),
        counters_close=None if close_text is None else parse(close_text))


def _reader(name):
    return cells._load_module(os.path.join(
        PERFBENCH, "staged", "readers", name + ".py")).read


@pytest.mark.parametrize("name, per_build", [
    ("session_hits_per_build", 1.5),
    ("session_invalidations_per_build", 3.0),
    ("session_restores_per_build", 3.5),
    ("layer_memo_replays_per_build", 4.0)])
def test_reader_reads_the_growth_a_counted_build(name, per_build):
    run = _record(_exposition(1), _exposition(2))
    assert _reader(name)(run) == pytest.approx(per_build)


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_without_counters(name):
    assert _reader(name)(_record(None, None)) is None
    assert _reader(name)(_record("", "")) is None
    assert _reader(name)(_record(_exposition(1), _exposition(2),
                                 counted=0)) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_worker_that_never_counted_one_reads_nought_where_the_layer_spoke(
        name):
    """No hit, no memo replay, no session dropped and no snapshot looked
    at leave no series behind; the builds that began under a session,
    and the layers left unread, say that the layer ran."""
    assert _reader(name)(_record(SESSIONLESS, SESSIONLESS)) == 0.0
