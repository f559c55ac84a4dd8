"""The ``monorepo-farm`` configuration as it is shipped
(``perfbench/configs/monorepo-farm.json``): one chip's share of the
farm's jobs (8 of 64 over a v5e-8), each building ``monorepo-slice``'s
context, all at once in one worker. The file equals ``monorepo-slice``
where the two must stay a pair; 4 lanes of the same tree at 384 KiB
build through one in-process ``WorkerServer(max_concurrent_builds=0)``
with the file's own flags, all started behind one barrier, cold and
then after one ``churn`` edit, and every build is held to
``perfbench/reference/cdc.py`` and hashlib and to the same 4 contexts
built one at a time; a producer blocked in ``HashService.submit`` adds
to ``service_submit`` and to ``service_wait`` alike, a batch of two
sessions observes 2 owners, and the four readers this deployment
brought read a run record.

Needs no ``/root/reference``, no C compiler, no inotify and no root.
"""

import hashlib
import json
import os
import shutil
import sys
import threading
import time
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
PERFBENCH = os.path.join(CHECKOUT, "perfbench")
sys.path.insert(0, PERFBENCH)

from pbharness import cells, check, driver, gen, stats  # noqa: E402

from makisu_tpu.chunker import service as service_mod  # noqa: E402
from makisu_tpu.chunker.cdc import ChunkSession  # noqa: E402
from makisu_tpu.utils import metrics  # noqa: E402
from makisu_tpu.worker import WorkerClient, WorkerServer  # noqa: E402


def _load(*parts):
    with open(os.path.join(CHECKOUT, *parts), encoding="utf-8") as f:
        return json.load(f)


CONFIG = _load("perfbench", "configs", "monorepo-farm.json")
SLICE = _load("perfbench", "configs", "monorepo-slice.json")
CONCURRENT = _load("perfbench", "configs", "farm-concurrent.json")
BENCHMARK = _load("BENCHMARK.json")
CHURN = _load("perfbench", "traffic", "churn.json")
CELL = "monorepo-farm-churn"
PAIRS = ("farm-concurrent-churn", "monorepo-edit")
PLAIN = cells._load_module(os.path.join(PERFBENCH, "reference", "cdc.py"))
NEW_READERS = ("worker_commit_mb_per_s", "commits_in_flight_mean",
               "hash_submit_blocked_s_per_build",
               "hash_builds_per_batch_mean")
RETIRED = {"sync_wait_share_pct", "chunk_store_share_pct",
           "commit_share_pct", "device_mb_per_build", "idle_unspanned_pct"}
BUSY = metrics.COMMIT_STAGE_BUSY
HASHED = "makisu_bytes_hashed_total"
_OLD = 1_600_000_000    # a time well before any test runs


# -- (a) the file, and its entries in BENCHMARK.json ------------------------


def _equals_monorepo_slice_where_they_are_a_pair():
    for key in ("context", "build_flags", "reference", "files",
                "total_bytes", "templates", "worker"):
        assert CONFIG[key] == SLICE[key], key
    assert CONFIG["guarantees"][:3] == SLICE["guarantees"]
    assert CONFIG["guarantees"][3] == (
        "a build's layers, cut points and digests are those of the same "
        "build run alone, whatever runs beside it")
    assert CONFIG["guarantees"][4] == CONCURRENT["guarantees"][3]
    assert len(CONFIG["guarantees"]) == 5
    for key in ("sizes", "content", "layers"):
        assert CONFIG["assumed"][key] == SLICE["assumed"][key], key
    assert CONFIG["assumed"]["kv"].startswith(SLICE["assumed"]["kv"])
    assert SLICE["reduced_why"] in CONFIG["reduced_why"]
    assert CONFIG["reference"] == "cdc"
    assert CONFIG["build_flags"] == ["--hasher", "tpu", "--commit",
                                     "explicit"]


def _states_what_a_deployment_states():
    for key in ("source", "deployment", "guarantees", "source_scale",
                "reduced", "reduced_why", "assumed"):
        assert CONFIG[key], key
    assert CONFIG["source_scale"] == {"chips": 8, "jobs": 64,
                                      "files": 100000,
                                      "total_bytes": "10 GB"}
    # One chip's share of the stated jobs, not a number of our own.
    scale = CONFIG["source_scale"]
    assert CONFIG["lanes"] == CONFIG["jobs_a_chip"] \
        == scale["jobs"] // scale["chips"] == 8
    assert CONFIG["chips"] == 1
    assert CONFIG["reduced"] == ["chips", "files", "total_bytes"]
    for cut in CONFIG["reduced"]:
        assert cut in CONFIG["reduced_why"], cut
    assert "one halving" in CONFIG["reduced_why"]
    assert CONFIG["worker"] == {"max_concurrent_builds": 0}
    assert CONFIG["templates"] == 0
    import inspect
    assert inspect.signature(WorkerServer.__init__) \
        .parameters["max_concurrent_builds"].default == 0
    for key in ("one_worker_a_host", "max_concurrent_builds"):
        assert CONFIG["assumed"][key] == CONCURRENT["assumed"][key], key
    for held in ("8 NativeLayerSinks", "two rings of 16 MiB", "256 MiB",
                 "8 resident sessions", "lane buffers", "memory_peak_bytes"):
        assert held in CONFIG["assumed"]["memory"], held
    assert "templates 0" in CONFIG["assumed"]["lanes"]
    for line in (3, 4):
        quoted = _load("BASELINE.json")["configs"][line]
        assert quoted.split(", Redis")[0] in CONFIG["source"], line


def _entry_and_cell_in_benchmark():
    entry = BENCHMARK["configs"][-1]
    assert entry["name"] == "monorepo-farm"
    assert entry["file"] == "perfbench/configs/monorepo-farm.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert CONFIG["source"].startswith(entry["source"])
    assert "configs[3]" in entry["source"] and "configs[4]" in entry["source"]
    assert _load("BASELINE.json")["configs"][4] in entry["source"]
    assert len({c["source"] for c in BENCHMARK["configs"]}) == 9
    cell = BENCHMARK["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "monorepo-farm", "churn", 1)
    assert len(cell["why"]) <= 200
    assert "builds a lane" in cell["why"]
    assert "compiles_in_window 0" in cell["why"]
    assert len(BENCHMARK["configs"]) == 9
    assert len(BENCHMARK["workloads"]) == 11
    assert all(w["chips"] == 1 for w in BENCHMARK["workloads"])
    # The mix is `farm-churn`'s, as it stands.
    assert (CHURN["count"], CHURN["prime_cold"], CHURN["prime_rebuilds"],
            CHURN["check_builds"], CHURN["measure_storage"]) \
        == ("completed", True, 1, 6, False)
    assert CHURN["edit"] == {"kind": "append", "share": 0.25,
                             "layer": "last", "text": "# edited {stamp}\n"}
    for m in BENCHMARK["end_to_end"]:
        assert m["bound"] == {"build_p50_s": 0.25, "build_mb_per_s": 0.15,
                              "stored_per_user_byte": 0.025,
                              "setup_s": 0.25}[m["name"]]
    assert BENCHMARK["run_seconds"] == 45


def _four_metrics_appended_with_their_cell():
    per_layer = BENCHMARK["per_layer"]
    # PR 51 added one after, PR 52 four, PR 53 one.
    assert [m["name"] for m in per_layer[67:71]] == list(NEW_READERS)
    assert len(per_layer) == 77
    by_name = {m["name"]: m for m in per_layer}
    commit = by_name["tar_write_s_per_build"]["layer"]
    batching = by_name["hash_batch_occupancy_pct"]["layer"]
    want = {
        "worker_commit_mb_per_s": ("MB/s", "higher", "program_counter",
                                   commit, "build_mb_per_s"),
        "commits_in_flight_mean": ("1", "lower", "program_span", commit,
                                   "build_p50_s"),
        "hash_submit_blocked_s_per_build": ("s", "lower", "program_counter",
                                            batching, "build_p50_s"),
        "hash_builds_per_batch_mean": ("1", "higher", "program_counter",
                                       batching, "build_mb_per_s")}
    for name, (unit, better, source, layer, moves) in want.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [CELL]}
    readers = os.listdir(os.path.join(PERFBENCH, "readers"))
    assert len([r for r in readers if r.endswith(".py")]) == 84
    assert metrics.HASH_BATCH_OWNERS == "makisu_hash_batch_owners"
    assert metrics.SERVICE_SUBMIT_STAGE == "service_submit"


def _cell_joins_the_lists_of_its_pairs():
    """Every standing metric ``farm-concurrent-churn`` or
    ``monorepo-edit`` reports, but the five that are to be retired and
    what moves ``stored_per_user_byte``, has the new cell appended
    last; no other list has it (PR 51's one, added after the cell,
    names it among the cells that apply a cached layer)."""
    for m in BENCHMARK["per_layer"][:67]:
        listed = m["workloads"]
        wanted = (m["name"] not in RETIRED
                  and m["moves"] != "stored_per_user_byte"
                  and any(p in listed for p in PAIRS))
        assert (CELL in listed) == wanted, m["name"]
        if wanted:
            assert listed[-1] == CELL and listed.count(CELL) == 1
    by_name = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    assert by_name["build_mb_per_s"]["workloads"][-1] == CELL
    assert CELL not in by_name["stored_per_user_byte"]["workloads"]


def _cell_reports_what_its_pairs_report_and_the_four():
    path = os.path.join(CHECKOUT, "BENCHMARK.json")
    ours = cells.Cell(path, CELL)
    assert ours.reference.__name__ == "perfbench_cdc"
    assert {m["name"] for m in ours.end_to_end()} \
        == {"build_p50_s", "build_mb_per_s", "setup_s"}
    mine = {m["name"] for m in ours.per_layer()}
    theirs = set()
    for pair in PAIRS:
        theirs |= {m["name"] for m in cells.Cell(path, pair).per_layer()}
    assert mine - theirs == set(NEW_READERS)
    assert theirs - mine == RETIRED | {"new_chunk_bytes_share_pct"}
    assert {"sha_hbm_roofline", "gear_hbm_roofline", "process_rss_peak_mb",
            "executing_builds_mean", "build_off_cpu_share_pct",
            "device_idle_pct", "compiles_in_window",
            "hash_service_wait_s_per_build", "commit_mb_per_s",
            "compress_wait_s_per_build"} <= mine
    for name in mine:
        assert callable(ours.reader(name)), name


@pytest.mark.parametrize("statement", [
    _equals_monorepo_slice_where_they_are_a_pair,
    _states_what_a_deployment_states, _entry_and_cell_in_benchmark,
    _four_metrics_appended_with_their_cell,
    _cell_joins_the_lists_of_its_pairs,
    _cell_reports_what_its_pairs_report_and_the_four],
    ids=lambda f: f.__name__.strip("_"))
def test_configuration_and_cell_are_declared(statement):
    statement()


# -- (b) 4 lanes of the same tree at 384 KiB, all executing at once --------


LANES = 4
FILES = 16          # a layer, where the file has 320


def _scaled_context():
    """Two layers of 16 files / 192 KiB where the file has 320 files /
    32 MiB: the same sizes' law, kinds of content and Dockerfile."""
    context = json.loads(json.dumps(CONFIG["context"]))
    for layer in context["layers"]:
        layer["files"] = FILES
        layer["bytes"] = 192 << 10
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
        self.server = WorkerServer(
            os.path.join(work, "w.sock"),
            max_concurrent_builds=CONFIG["worker"]["max_concurrent_builds"])
        self.thread = self.server.serve_background()
        self.control = WorkerClient(self.server.socket_path)
        deadline = time.monotonic() + 60
        while not self.control.ready():
            assert time.monotonic() < deadline
            time.sleep(0.02)
        self.contexts = []
        for lane in range(LANES):
            ctx = os.path.join(work, f"lane{lane}", "ctx")
            # templates 0: each lane its own content, as the driver
            # seeds it.
            gen.make_tree(context, ctx, np.random.SeedSequence(
                [50, lane, 0]).generate_state(1)[0])
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
                         tag=f"monorepofarm/lane{lane}:{how}-{kind}",
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
        tree aged again, to a later time than before (layer a stays a
        cache hit, with the times it was committed with)."""
        for lane, ctx in enumerate(self.contexts):
            touched = gen.apply_edit(
                CHURN["edit"], self.context, ctx,
                np.random.default_rng([50, lane, 7]), "000001")
            assert touched == FILES // 4
            _age(os.path.join(ctx, "b"), _OLD + 3600)

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
    work = str(tmp_path_factory.mktemp("monorepofarm"))
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
def test_builds_at_once_held_to_the_reference(farm, kind, count):
    assert [b.exit_code for b in farm[kind, "together"]] == [0] * LANES
    checker = farm[kind, "together", "check"]
    assert checker.found[count] == 0, checker.notes
    assert checker.checked["builds"] == LANES
    assert checker.checked["layers"] == 2 * LANES
    assert checker.checked["chunks"] > LANES * 16
    assert checker.verdict()


@pytest.mark.parametrize("kind", ["cold", "rebuild"])
def test_a_build_among_four_stores_what_it_stores_alone(farm, kind):
    """The configuration's fourth guarantee: blob and tar digests and
    every chunk's offset, length and fingerprint equal those of the
    same context built with nothing beside it, and each lane's content
    is its own."""
    alone = farm[kind, "alone", "check"]
    assert alone.verdict(), alone.notes
    together, by_itself = (farm[kind, how, "outputs"]
                           for how in ("together", "alone"))
    assert together == by_itself
    for layers in together:
        assert len(layers) == 2 and all(chunks for _, _, chunks in layers)
    for a in range(LANES):
        for b in range(a):
            assert together[a][0][0] != together[b][0][0]
            assert together[a][1][0] != together[b][1][0]


def test_the_edit_left_layer_a_a_cache_hit(farm):
    for cold, edited in zip(farm["cold", "together", "outputs"],
                            farm["rebuild", "together", "outputs"]):
        assert cold[0] == edited[0] and cold[1] != edited[1]
    for b in farm["rebuild", "together"]:
        assert [name for name, _ in b.spans].count("commit_layer") == 1
    for b in farm["cold", "together"]:
        assert [name for name, _ in b.spans].count("commit_layer") == 2


@pytest.mark.parametrize("kind", ["cold", "rebuild"])
def test_no_build_waited_for_admission_and_they_overlapped(farm, kind):
    for b in farm[kind, "together"]:
        assert b.terminal["queue_wait_seconds"] == 0
        assert b.terminal["service_seconds"] <= b.seconds
    t_open, t_close = farm[kind, "window"]
    assert sum(b.terminal["service_seconds"]
               for b in farm[kind, "together"]) > 1.5 * (t_close - t_open)


# -- (c) the backpressure beside the wait, and a batch's owners ------------


@pytest.fixture
def build_registry():
    registry = metrics.MetricsRegistry()
    token = metrics.set_build_registry(registry)
    yield registry
    metrics.reset_build_registry(token)


def _owners():
    series = metrics.global_registry().report()["histograms"].get(
        metrics.HASH_BATCH_OWNERS, [])
    return (sum(s["count"] for s in series), sum(s["sum"] for s in series))


def test_a_producer_blocked_in_submit_counts_beside_the_wait(
        monkeypatch, build_registry):
    """Queues of two chunks, and a dispatcher held inside its first
    batch for 0.3 s: the fourth ``_emit`` blocks in ``submit``. Those
    seconds are ``service_submit`` and are in ``service_wait`` as they
    were; the wait for the futures is in ``service_wait`` alone; an
    enclosing stage is charged them once."""
    real = service_mod.queue
    monkeypatch.setattr(service_mod, "queue", types.SimpleNamespace(
        Queue=lambda maxsize=0: real.Queue(maxsize=2), Empty=real.Empty))
    svc = service_mod.HashService(linger_seconds=0.0)
    gate = threading.Event()
    run_batch = svc._run_batch

    def held(cap, lanes, batch):
        gate.wait(30)
        run_batch(cap, lanes, batch)

    svc._run_batch = held
    payloads = [bytes([i]) * 3000 for i in range(6)]
    try:
        session = ChunkSession(block=64 * 1024, service=svc)
        clock = session._clock
        threading.Timer(0.3, gate.set).start()
        with clock.stage("host_cut"):
            for i, data in enumerate(payloads):
                session._emit(data, 3000 * i)
        blocked = clock.seconds[metrics.SERVICE_SUBMIT_STAGE]
        assert 0.2 < blocked < 5
        assert clock.seconds["service_wait"] == pytest.approx(blocked)
        # Charged to itself alone, once: the enclosing stage keeps what
        # it had before there was a second name for these seconds.
        assert clock.seconds["host_cut"] < 0.1
        chunks = session.finish()
    finally:
        gate.set()
        svc.close()
    assert [c.digest for c in chunks] \
        == [hashlib.sha256(p).digest() for p in payloads]
    submit, wait = (build_registry.counter_total(BUSY, stage=stage)
                    for stage in (metrics.SERVICE_SUBMIT_STAGE,
                                  "service_wait"))
    assert submit == pytest.approx(blocked)
    assert wait > submit


def test_a_batch_of_two_sessions_observes_two_owners():
    svc = service_mod.HashService(linger_seconds=0.5)
    try:
        count, total = _owners()
        a, b = object(), object()
        futures = [svc.submit(b"a" * 3000, owner=id(a)),
                   svc.submit(b"b" * 3000, owner=id(b)),
                   svc.submit(b"c" * 3000, owner=id(a))]
        for fut in futures:
            fut.result(timeout=120)
        assert svc.batches == 1 and svc.cross_build_batches == 1
        assert _owners() == (count + 1, total + 2)
        # A build riding alone observes 1.
        svc.submit(b"d" * 3000, owner=id(a)).result(timeout=120)
        assert _owners() == (count + 2, total + 3)
    finally:
        svc.close()


# -- (d) the four readers ---------------------------------------------------


def _run_record(farm, program_side=True):
    run = driver.Run(cell=None, seed=50, seconds=45.0, trace=True)
    run.builds = list(farm["rebuild", "together"])
    run.counted = list(run.builds)
    run.t_open, run.t_close = farm["rebuild", "window"]
    run.counters_open, run.counters_close = farm["rebuild", "counters"]
    if not program_side:
        # A program without the series and the span (the parent's side
        # of the driver's pair lacks the stage and the histogram).
        run.builds = run.counted = [
            driver.Build(**{**vars(b), "spans": [
                s for s in b.spans if s[0] != "commit_layer"]})
            for b in run.counted]
        run.counters_open, run.counters_close = (
            {key: v for key, v in counters.items()
             if not key[0].startswith(metrics.HASH_BATCH_OWNERS)
             and ("stage", metrics.SERVICE_SUBMIT_STAGE) not in key[1]
             and ("path", "layer_sink") not in key[1]}
            for counters in (run.counters_open, run.counters_close))
    return run


def _reader(name):
    return cells._load_module(
        os.path.join(PERFBENCH, "readers", name + ".py")).read


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_reads_a_run_record(farm, name):
    run = _run_record(farm)
    got = _reader(name)(run)

    def delta(series, **labels):
        return stats.counter_delta(run.counters_open, run.counters_close,
                                   series, **labels)

    commits = [float(d) for b in run.counted for n, d in b.spans
               if n == "commit_layer"]
    assert len(commits) == LANES
    want = {
        "worker_commit_mb_per_s":
            delta(HASHED, path="layer_sink") / 1e6 / run.window_s,
        "commits_in_flight_mean": sum(commits) / run.window_s,
        "hash_submit_blocked_s_per_build":
            delta(BUSY, stage=metrics.SERVICE_SUBMIT_STAGE) / LANES,
        "hash_builds_per_batch_mean":
            delta(metrics.HASH_BATCH_OWNERS + "_sum")
            / delta(metrics.HASH_BATCH_OWNERS + "_count"),
    }[name]
    assert got == pytest.approx(want) and got > 0
    if name == "worker_commit_mb_per_s":
        # Each rebuild committed layer b alone: its tar, a little over
        # its files' bytes.
        tars = delta(HASHED, path="layer_sink")
        assert LANES * (192 << 10) < tars < LANES * (256 << 10)
    if name == "commits_in_flight_mean":
        assert got <= _reader("executing_builds_mean")(run) <= LANES
    if name == "hash_submit_blocked_s_per_build":
        assert got <= _reader("hash_service_wait_s_per_build")(run)
    if name == "hash_builds_per_batch_mean":
        assert 1 <= got <= LANES


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_where_the_program_says_nothing(farm, name):
    read = _reader(name)
    assert read(_run_record(farm, program_side=False)) is None
    empty = _run_record(farm)
    empty.builds = empty.counted = []
    # The batches are the service's whoever is counted.
    assert (read(empty) is None) == (name != "hash_builds_per_batch_mean")
    untraced = _run_record(farm)
    untraced.counters_open = untraced.counters_close = None
    assert (read(untraced) is None) == (name != "commits_in_flight_mean")
