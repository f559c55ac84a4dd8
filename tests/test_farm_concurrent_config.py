"""The ``farm-concurrent`` configuration as it is shipped
(``perfbench/configs/farm-concurrent.json``): it is ``farm`` with the
admission queue taken away and, by ISSUE 38's one allowed halving, 16 of
its 32 lanes, and nothing else, so the two cells stay a pair; 8 lanes of the same tree at 96 KiB build through one in-process
``WorkerServer(max_concurrent_builds=0)`` with the file's own flags, all
started behind one barrier, cold and then after one ``churn`` edit, and
every build is held to ``perfbench/reference/cdc.py`` and hashlib and to
the same 8 contexts built one at a time (a batch that mixes builds gives
each build its own digests); no build waited for admission, each
terminal record carries its thread's CPU seconds, and the six readers
this deployment brought read a run record.

Needs no ``/root/reference``, no C compiler, no inotify and no root.
"""

import json
import os
import shutil
import sys
import threading
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
PERFBENCH = os.path.join(CHECKOUT, "perfbench")
sys.path.insert(0, PERFBENCH)

from pbharness import cells, check, driver, gen, stats  # noqa: E402

from makisu_tpu.utils import metrics  # noqa: E402
from makisu_tpu.worker import WorkerClient, WorkerServer  # noqa: E402


def _load(*parts):
    with open(os.path.join(CHECKOUT, *parts), encoding="utf-8") as f:
        return json.load(f)


CONFIG = _load("perfbench", "configs", "farm-concurrent.json")
FARM = _load("perfbench", "configs", "farm.json")
BENCHMARK = _load("BENCHMARK.json")
CHURN = _load("perfbench", "traffic", "churn.json")
CELL = "farm-concurrent-churn"
PLAIN = cells._load_module(os.path.join(PERFBENCH, "reference", "cdc.py"))
NEW_READERS = ("build_thread_cpu_s_per_build", "build_off_cpu_share_pct",
               "executing_builds_mean", "unspanned_cpu_s_per_build",
               "hash_service_wait_s_per_build", "hash_cross_build_batch_pct")
CROSS = "makisu_hash_cross_build_batches_total"
BATCHES = "makisu_hash_batches_total"
_OLD = 1_600_000_000    # a time well before any test runs


# -- (a) the pair stays a pair ----------------------------------------------


def _farm_with_the_queue_taken_away_and_half_the_lanes():
    same = ("context", "templates", "zipf", "build_flags", "reference",
            "files", "total_bytes", "source_scale", "chips")
    for key in same:
        assert CONFIG[key] == FARM[key], key
    assert sorted(k for k in set(CONFIG) | set(FARM)
                  if CONFIG.get(k) != FARM.get(k)) == [
        "assumed", "deployment", "guarantees", "jobs", "lanes", "name",
        "reduced", "reduced_why", "source", "worker"]
    # ISSUE 38's one allowed change of size, taken: half of `farm`'s
    # lanes (the file's `reduced_why` has the two readings).
    assert CONFIG["lanes"] == CONFIG["jobs"] == FARM["lanes"] // 2 == 16
    for reading in ("0.137", "0.107", "82 to 99", "one halving"):
        assert reading in CONFIG["reduced_why"], reading
    assert FARM["worker"] == {"max_concurrent_builds": 4}
    assert CONFIG["worker"] == {"max_concurrent_builds": 0}
    # The worker's own default, not a number of this benchmark's.
    import inspect
    assert inspect.signature(WorkerServer.__init__) \
        .parameters["max_concurrent_builds"].default == 0


def _states_what_a_deployment_states():
    for key in ("source", "deployment", "guarantees", "source_scale",
                "reduced", "reduced_why", "assumed"):
        assert CONFIG[key], key
    assert CONFIG["guarantees"][:3] == FARM["guarantees"][:3]
    assert "admission is FIFO" in FARM["guarantees"][3]
    assert CONFIG["guarantees"][3] == (
        "no build waits for admission, and none is refused, dropped or "
        "failed for want of a file descriptor, a watch, a thread or memory")
    assert CONFIG["reduced"] == ["chips", "jobs", "files", "total_bytes"]
    for cut in CONFIG["reduced"]:
        assert cut in CONFIG["reduced_why"], cut
    assert CONFIG["source_scale"] == {"chips": 8, "jobs": 64}
    assert CONFIG["assumed"]["context"] == FARM["assumed"]["context"]
    assert CONFIG["assumed"]["templates"].startswith(
        FARM["assumed"]["templates"] + "; at 16 lanes that deal is ")
    # What the deal became, as the harness deals it.
    deal = [driver.template_of(lane, CONFIG) for lane in range(16)]
    assert deal == [0, 0, 0, 0, 0, 1, 1, 2, 2, 3, 4, 5, 7, 9, 11, 14]
    for said in ("5 lanes on template 0", "3, 4, 5, 7, 9, 11 and 14",
                 "10 of the 16 templates"):
        assert said in CONFIG["assumed"]["templates"], said
    assert CONFIG["assumed"]["max_concurrent_builds"].startswith("0, ")
    for held in ("16 MiB a sink", "256 MiB", "16 resident sessions",
                 "lane buffers", "memory_peak_bytes"):
        assert held in CONFIG["assumed"]["memory"], held
    assert "one long-lived worker process a host" \
        in CONFIG["assumed"]["one_worker_a_host"]


def _entry_and_cell_in_benchmark():
    # The last of both lists at its PR; PR 41 appended after it.
    entry = BENCHMARK["configs"][5]
    assert entry["name"] == "farm-concurrent"
    assert entry["file"] == "perfbench/configs/farm-concurrent.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert CONFIG["source"].startswith(entry["source"])
    assert _load("BASELINE.json")["configs"][4] in entry["source"]
    [farm] = [c for c in BENCHMARK["configs"] if c["name"] == "farm"]
    assert entry["source"] != farm["source"]
    cell = BENCHMARK["workloads"][7]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "farm-concurrent", "churn", 1)
    assert len(cell["why"]) <= 200
    # PR 41 added a configuration and a cell after this one, PRs 47
    # and 50 too.
    assert len(BENCHMARK["configs"]) == 9
    assert len(BENCHMARK["workloads"]) == 11
    assert all(w["chips"] == 1 for w in BENCHMARK["workloads"])
    # The mix is `farm-churn`'s, as it stands.
    assert (CHURN["count"], CHURN["prime_cold"], CHURN["prime_rebuilds"],
            CHURN["check_builds"]) == ("completed", True, 1, 6)
    assert CHURN["edit"]["share"] == 0.25


def _cell_reports_what_farm_churn_reports_and_the_six():
    ours = cells.Cell(os.path.join(CHECKOUT, "BENCHMARK.json"), CELL)
    theirs = cells.Cell(os.path.join(CHECKOUT, "BENCHMARK.json"),
                        "farm-churn")
    assert ours.reference.__name__ == "perfbench_cdc"
    assert {m["name"] for m in ours.end_to_end()} \
        == {"build_p50_s", "build_mb_per_s", "setup_s"}
    mine = {m["name"] for m in ours.per_layer()}
    assert mine == {m["name"] for m in theirs.per_layer()} \
        | {"process_rss_peak_mb"}
    assert set(NEW_READERS) | {"sha_hbm_roofline", "gear_hbm_roofline",
                               "queue_wait_p50_s", "hash_batch_occupancy_pct",
                               "device_idle_pct", "sink_prefetch_ready_pct",
                               "read_wait_s_per_build"} <= mine
    for name in mine:
        assert callable(ours.reader(name)), name


def _new_metrics_list_the_three_farm_cells():
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    # PR 40 added two after, PR 41 four, PR 42 one, PR 45 one, PR 47
    # four, PR 48 one, PR 49 one, PR 50 four, PR 51 one, PR 52 four,
    # PR 53 one.
    assert names[47:53] == list(NEW_READERS)
    assert len(names) == 77
    by_name = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in NEW_READERS:
        # PR 50's cell joined the lists of this one.
        assert by_name[name]["workloads"] == [
            "farm-churn", "farm-unchanged", CELL,
            "monorepo-farm-churn"], name
    admission = by_name["queue_wait_p50_s"]["layer"]
    assert [(by_name[n]["layer"], by_name[n]["moves"], by_name[n]["better"],
             by_name[n]["source"], by_name[n]["unit"])
            for n in NEW_READERS] == [
        (admission, "build_p50_s", "lower", "program_span", "s"),
        (admission, "build_p50_s", "lower", "program_span", "%"),
        (admission, "build_p50_s", "lower", "host_clock", "1"),
        (by_name["unspanned_s_per_build"]["layer"], "build_p50_s", "lower",
         "program_counter", "s"),
        (by_name["hash_batch_occupancy_pct"]["layer"], "build_p50_s",
         "lower", "program_counter", "s"),
        (by_name["hash_batch_occupancy_pct"]["layer"], "build_mb_per_s",
         "higher", "program_counter", "%")]
    # Appended, never inserted: the cell is the last of every list it
    # joined, and it joined every list `farm-churn` is on.
    for m in BENCHMARK["per_layer"][:47] + BENCHMARK["end_to_end"]:
        listed = [w for w in m.get("workloads", ())
                  if w not in ("run-steps-edit", "huge-layer-pgzip-edit",
                               "monorepo-farm-churn")]
        if CELL in listed:
            assert listed[-1] == CELL, m["name"]
        if "workloads" in m:
            assert (CELL in listed) == ("farm-churn" in listed
                                        or m["name"] == "process_rss_peak_mb")
    assert metrics.SPAN_SELF_CPU_SECONDS \
        == "makisu_span_self_cpu_seconds_total"
    assert metrics.WORKER_BUILD_THREAD_CPU_SECONDS \
        == "makisu_worker_build_thread_cpu_seconds_total"


@pytest.mark.parametrize("statement", [
    _farm_with_the_queue_taken_away_and_half_the_lanes,
    _states_what_a_deployment_states, _entry_and_cell_in_benchmark,
    _cell_reports_what_farm_churn_reports_and_the_six,
    _new_metrics_list_the_three_farm_cells],
    ids=lambda f: f.__name__.strip("_"))
def test_configuration_and_cell_are_declared(statement):
    statement()


# -- (b) 8 lanes of the same tree at 96 KiB, all executing at once ---------


LANES = 8


def _scaled_context():
    """8 + 24 files / 24 + 72 KiB where the file has 32 + 96 files /
    512 KiB + 1.5 MiB: a file of 3 KiB still holds a chunk."""
    context = json.loads(json.dumps(CONFIG["context"]))
    for layer in context["layers"]:
        layer["files"] //= 4
        layer["bytes"] = layer["bytes"] * 3 // 64
    assert sum(layer["bytes"] for layer in context["layers"]) == 96 << 10
    return context


def _age(tree, when):
    """Every file and directory of ``tree`` gets a fixed mode and an
    mtime of long ago, so that its tars are the same whenever they are
    made and no layer waits out an mtime."""
    for parent, _dirs, names in os.walk(tree, topdown=False):
        for name in names:
            os.chmod(os.path.join(parent, name), 0o644)
            os.utime(os.path.join(parent, name), (when, when))
        os.chmod(parent, 0o755)
        os.utime(parent, (when, when))


class _Farm:
    """One worker with no admission limit and ``LANES`` contexts, each
    with a storage for the builds made all at once and another for the
    builds made one at a time."""

    def __init__(self, work, context):
        self.work = work
        self.context = context
        self.server = WorkerServer(os.path.join(work, "w.sock"),
                                   max_concurrent_builds=0)
        self.thread = self.server.serve_background()
        self.control = WorkerClient(self.server.socket_path)
        deadline = time.monotonic() + 60
        while not self.control.ready():
            assert time.monotonic() < deadline
            time.sleep(0.02)
        deal = dict(CONFIG, lanes=LANES)
        self.contexts = []
        for lane in range(LANES):
            ctx = os.path.join(work, f"lane{lane}", "ctx")
            # Lanes of one template start from equal content, as the
            # driver deals them.
            gen.make_tree(context, ctx, np.random.SeedSequence(
                [38, driver.template_of(lane, deal), 0]).generate_state(1)[0])
            _age(ctx, _OLD)
            self.contexts.append(ctx)
        self.built = 0

    def counters(self):
        return stats.parse_prometheus(self.control.metrics())

    def _build(self, lane, kind, how, gate=None):
        n, self.built = self.built, self.built + 1
        root = os.path.join(self.work, f"root{n}")
        os.makedirs(root)
        storage = os.path.join(self.work, f"lane{lane}", f"storage-{how}")
        b = driver.Build(lane=lane, index=n, kind=kind,
                         tag=f"farmconcurrent/lane{lane}:{how}-{kind}",
                         context=self.contexts[lane], storage=storage,
                         context_bytes=gen.tree_bytes(self.contexts[lane]))
        client = WorkerClient(self.server.socket_path)
        if gate is not None:
            gate.wait(timeout=120)
        b.t_submit = time.monotonic()
        while True:
            try:
                b.exit_code = client.build(
                    ["--log-level", "error", "build", b.context, "-t", b.tag,
                     "--storage", storage, "--root", root]
                    + list(CONFIG["build_flags"]))
                break
            except driver._CONNECT_ERRORS:
                # Eight connects in one instant can overrun the socket's
                # backlog: submitted again, as the harness's lanes do.
                b.retries += 1
                assert b.retries < 500
                time.sleep(0.02)
        b.t_done = time.monotonic()
        b.terminal = dict(client.last_build)
        b.spans = [(e.get("name"), e.get("duration"))
                   for e in client.last_events if e.get("type") == "span_end"]
        b.counted = kind == "rebuild"
        shutil.rmtree(root, ignore_errors=True)
        return b

    def all_at_once(self, kind):
        gate = threading.Barrier(LANES)
        out = [None] * LANES

        def lane_main(lane):
            out[lane] = self._build(lane, kind, "together", gate)

        threads = [threading.Thread(target=lane_main, args=(lane,))
                   for lane in range(LANES)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
            assert not th.is_alive()
        return out

    def one_at_a_time(self, kind):
        return [self._build(lane, kind, "alone") for lane in range(LANES)]

    def edit(self):
        """The mix's own edit on every lane, then the edited layer's
        tree aged again, to a later time than before (the lower layer
        stays a cache hit, with the times it was committed with)."""
        for lane, ctx in enumerate(self.contexts):
            touched = gen.apply_edit(
                CHURN["edit"], self.context, ctx,
                np.random.default_rng([38, lane, 7]), "000001")
            assert touched == 24 // 4
            _age(os.path.join(ctx, "src"), _OLD + 3600)

    def close(self):
        self.server.shutdown()
        self.server.server_close()
        self.thread.join(timeout=5)


def _held_to_reference(context, builds):
    checker = check.Checker(PLAIN, context)
    for b in builds:
        checker.check_build(b, tree_is_current=True)
    return checker


def _outputs(b):
    """What a build stored, layer by layer: blob digest, tar digest and
    the chunk list [(offset, length, fingerprint)]."""
    manifest, config, entries = check.Checker(None, {})._manifest(b)
    return [(layer["digest"], diff_id,
             [tuple(c) for c in entries[layer["digest"]]["chunks"]])
            for layer, diff_id in zip(manifest["layers"],
                                      config["rootfs"]["diff_ids"])]


@pytest.fixture(scope="module")
def farm(tmp_path_factory):
    work = str(tmp_path_factory.mktemp("farmconcurrent"))
    context = _scaled_context()
    out = {"context": context, "work": work}
    f = _Farm(work, context)
    try:
        for kind in ("cold", "rebuild"):
            if kind == "rebuild":
                f.edit()
            before = f.counters()
            t_open = time.monotonic()
            out[kind, "together"] = f.all_at_once(kind)
            t_close = time.monotonic()
            out[kind, "counters"] = (before, f.counters())
            out[kind, "window"] = (t_open, t_close)
            out[kind, "alone"] = f.one_at_a_time(kind)
            # While the trees are the trees these builds built.
            for how in ("together", "alone"):
                out[kind, how, "check"] = _held_to_reference(
                    context, out[kind, how])
                out[kind, how, "outputs"] = [_outputs(b)
                                             for b in out[kind, how]]
    finally:
        f.close()
    return out


@pytest.mark.parametrize("count", sorted(check.LIMITS))
@pytest.mark.parametrize("kind", ["cold", "rebuild"])
def test_concurrent_builds_held_to_the_reference(farm, kind, count):
    assert [b.exit_code for b in farm[kind, "together"]] == [0] * LANES
    checker = farm[kind, "together", "check"]
    assert checker.found[count] == 0, checker.notes
    assert checker.checked["builds"] == LANES
    assert checker.checked["members"] == LANES * 32
    assert checker.checked["chunks"] > LANES * 8
    assert checker.verdict()


@pytest.mark.parametrize("kind", ["cold", "rebuild"])
def test_a_build_among_eight_stores_what_it_stores_alone(farm, kind):
    """A batch of the shared hash service that mixes builds gives each
    build its own digests: blob and tar digests and every chunk's
    offset, length and fingerprint equal those of the same context
    built with nothing beside it."""
    alone = farm[kind, "alone", "check"]
    assert alone.verdict(), alone.notes
    together, by_itself = (farm[kind, how, "outputs"]
                           for how in ("together", "alone"))
    assert together == by_itself
    for layers in together:
        assert len(layers) == 2 and all(chunks for _, _, chunks in layers)
    # Lanes of one template built equal trees, the others did not.
    deal = dict(CONFIG, lanes=LANES)
    templates = [driver.template_of(lane, deal) for lane in range(LANES)]
    assert len(set(templates)) > 1 and len(set(templates)) < LANES
    if kind == "cold":
        for a in range(LANES):
            for b in range(a):
                assert (together[a] == together[b]) \
                    == (templates[a] == templates[b])


def test_the_edit_left_the_lower_layer_a_cache_hit(farm):
    for cold, edited in zip(farm["cold", "together", "outputs"],
                            farm["rebuild", "together", "outputs"]):
        assert cold[0] == edited[0] and cold[1] != edited[1]


@pytest.mark.parametrize("kind", ["cold", "rebuild"])
def test_builds_shared_batches_of_the_hash_service(farm, kind):
    before, after = farm[kind, "counters"]
    assert stats.counter_delta(before, after, BATCHES) > 0
    assert stats.counter_delta(before, after, CROSS) > 0
    assert stats.counter_delta(before, after, CROSS) \
        <= stats.counter_delta(before, after, BATCHES)


@pytest.mark.parametrize("how", ["together", "alone"])
@pytest.mark.parametrize("kind", ["cold", "rebuild"])
def test_no_build_waited_for_admission_and_each_says_its_cpu(farm, kind, how):
    for b in farm[kind, how]:
        terminal = b.terminal
        assert terminal["queue_wait_seconds"] == 0
        assert 0.0 < terminal["thread_cpu_seconds"] \
            <= terminal["service_seconds"] + 0.005
        assert terminal["service_seconds"] <= b.seconds
    if how == "together":
        # They did execute at once: the eight services overlap.
        t_open, t_close = farm[kind, "window"]
        assert sum(b.terminal["service_seconds"]
                   for b in farm[kind, how]) > 2 * (t_close - t_open)


# -- (c) the six readers ----------------------------------------------------


def _run_record(farm, program_side=True):
    run = driver.Run(cell=None, seed=38, seconds=45.0, trace=True)
    run.builds = list(farm["rebuild", "together"])
    run.counted = list(run.builds)
    run.t_open, run.t_close = farm["rebuild", "window"]
    run.counters_open, run.counters_close = farm["rebuild", "counters"]
    if not program_side:
        # A worker from before this deployment was supported (the
        # parent's side of the driver's pair), and one from before its
        # records said their service: no field, no series.
        strip = ("thread_cpu_seconds", "service_seconds")
        run.builds = run.counted = [
            driver.Build(**{**vars(b), "terminal": {
                k: v for k, v in b.terminal.items() if k not in strip}})
            for b in run.counted]
        gone = (metrics.SPAN_SELF_CPU_SECONDS, BATCHES, CROSS)
        run.counters_open, run.counters_close = (
            {key: v for key, v in counters.items()
             if key[0] not in gone
             and ("stage", "service_wait") not in key[1]}
            for counters in (run.counters_open, run.counters_close))
    return run


def _reader(name):
    return cells._load_module(
        os.path.join(PERFBENCH, "readers", name + ".py")).read


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_reads_a_run_record(farm, name):
    run = _run_record(farm)
    got = _reader(name)(run)
    builds = run.counted
    cpu = [b.terminal["thread_cpu_seconds"] for b in builds]
    service = [b.terminal["service_seconds"] for b in builds]

    def delta(series, **labels):
        return stats.counter_delta(run.counters_open, run.counters_close,
                                   series, **labels)

    want = {
        "build_thread_cpu_s_per_build": sum(cpu) / LANES,
        "build_off_cpu_share_pct": 100 * (1 - sum(cpu) / sum(service)),
        "executing_builds_mean": sum(service) / run.window_s,
        "unspanned_cpu_s_per_build":
            delta(metrics.SPAN_SELF_CPU_SECONDS) / LANES,
        "hash_service_wait_s_per_build":
            delta(metrics.COMMIT_STAGE_BUSY, stage="service_wait") / LANES,
        "hash_cross_build_batch_pct": 100 * delta(CROSS) / delta(BATCHES),
    }[name]
    assert got == pytest.approx(want) and got > 0
    if name == "build_off_cpu_share_pct":
        assert 0 < got < 100
    if name == "executing_builds_mean":
        assert 2 < got <= LANES
    if name == "unspanned_cpu_s_per_build":
        wall = _reader("unspanned_s_per_build")(run)
        assert got <= wall + 0.005


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_where_the_program_says_nothing(farm, name):
    read = _reader(name)
    assert read(_run_record(farm, program_side=False)) is None
    empty = _run_record(farm)
    empty.builds = empty.counted = []
    # The batches are the service's whoever is counted.
    assert (read(empty) is None) == (name != "hash_cross_build_batch_pct")
    untraced = _run_record(farm)
    untraced.counters_open = untraced.counters_close = None
    if name in ("unspanned_cpu_s_per_build", "hash_service_wait_s_per_build",
                "hash_cross_build_batch_pct"):
        assert read(untraced) is None
    else:
        assert read(untraced) is not None
