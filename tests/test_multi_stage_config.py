"""The ``multi-stage-small`` configuration as it is shipped
(``perfbench/configs/multi-stage-small.json``): the generator gives the
sizes and the five tar lengths the file states; the same tree at
3 x 96 KiB builds through a worker with the file's own flags and is held
to the configuration's own reference (``perfbench/reference/
cdc_stages.py``, which interprets the Dockerfile); a rebuild after one
edit commits four layers, unpacks one and opens the spans and counters
this deployment brought; the reference's interpreter agrees with the
program on a set of Dockerfiles; a tampered tree, a swapped destination
and a lost mtime read as differences; the CPU hasher agrees; the build's
cache ids, tar digests and chunk lists are those the parent commit gave;
and the new readers read a run record.

Needs no ``/root/reference``, no C compiler, no inotify and no root.
"""

import contextlib
import hashlib
import json
import os
import shutil
import sys
import time
import zlib

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
PERFBENCH = os.path.join(CHECKOUT, "perfbench")
sys.path.insert(0, PERFBENCH)

from pbharness import cells, check, driver, gen, stats  # noqa: E402

from makisu_tpu import cli  # noqa: E402
from makisu_tpu.snapshot import memfs  # noqa: E402
from makisu_tpu.utils import metrics  # noqa: E402
from makisu_tpu.worker import WorkerClient, WorkerServer  # noqa: E402


def _load(*parts):
    with open(os.path.join(CHECKOUT, *parts), encoding="utf-8") as f:
        return json.load(f)


def _module(*parts):
    return cells._load_module(os.path.join(PERFBENCH, *parts))


CONFIG = _load("perfbench", "configs", "multi-stage-small.json")
BENCHMARK = _load("BENCHMARK.json")
EDIT = _load("perfbench", "traffic", "edit.json")
CELL = "multi-stage-small-edit"
STAGES = _module("reference", "cdc_stages.py")
PLAIN = _module("reference", "cdc.py")
NEW_READERS = ("stage_transition_s_per_build", "copy_on_disk_s_per_build",
               "on_disk_mb_per_build", "layer_commits_per_build",
               "mtime_wait_slept_per_build", "session_finish_s_per_build")
ON_DISK = "makisu_on_disk_bytes_total"
UNTARRED = "makisu_untar_members_total"
COMMITS = "makisu_layer_commits_total"
REPLAY = "makisu_layer_replay_total"
SLEPT = "makisu_mtime_wait_total"

# tar's framing, as the program writes it: a 512-byte header an entry,
# content padded to 512, two closing blocks, the whole padded to
# tarfile's 10,240-byte record.
_BLOCK, _RECORD = 512, 10240
_GEAR_BLOCK, _GEAR_QUANTUM = 4 << 20, 64 << 10
# A time well before any test runs: no layer waits out an mtime.
_OLD = 1_600_000_000


def _tar_bytes(plan, layer_dir, dest_dirs=2):
    """(padded, raw) length of the layer tar of one generated directory:
    its files, their sub-directories and both destination directories
    (``/workspace/deps/`` or ``/makisu-internal/lib/``: each layer
    writes its destination's ancestors again)."""
    mine = [e for e in plan if e["layer"] == layer_dir]
    dirs = len({os.path.dirname(e["path"]) for e in mine}) + dest_dirs
    raw = sum(-(-e["size"] // _BLOCK) * _BLOCK for e in mine) \
        + _BLOCK * (len(mine) + dirs) + 2 * _BLOCK
    return -(-raw // _RECORD) * _RECORD, raw


# -- (a) the shapes the file states, at full size --------------------------


def _three_directories_of_one_mebibyte(plan):
    layers = CONFIG["context"]["layers"]
    assert [(s["dir"], s["dest"], s["files"], s["bytes"]) for s in layers] == [
        ("assets", "/makisu-internal/certs/", 16, 1048576),
        ("deps", "/makisu-internal/lib/", 96, 1048576),
        ("bin", "/makisu-internal/bin/", 4, 1048576)]
    for spec in layers:
        sizes = [e["size"] for e in plan if e["layer"] == spec["dir"]]
        assert (len(sizes), sum(sizes)) == (spec["files"], spec["bytes"])
    assert CONFIG["total_bytes"] == 3 * CONFIG["layer_bytes"] == 3145728
    assert (CONFIG["stages"], CONFIG["commit_layers"]) == (2, 5)


def _sizes_are_the_stated_ranges(plan):
    stated = CONFIG["assumed"]["sizes"]
    for layer_dir in ("assets", "deps", "bin"):
        sizes = [e["size"] for e in plan if e["layer"] == layer_dir]
        assert f"{min(sizes):,} to {max(sizes):,} bytes" in stated, layer_dir


def _every_file_of_bin_can_be_drawn_by_the_edit(plan):
    assert CONFIG["context"]["layers"][-1]["dir"] == "bin"
    assert EDIT["edit"]["layer"] == "last"
    sizes = [e["size"] for e in plan if e["layer"] == "bin"]
    assert len(sizes) == 4
    assert min(sizes) >= EDIT["edit"]["min_file_bytes"] == 65536


def _three_files_of_text_to_one_of_random_bytes(plan):
    assert CONFIG["context"]["content"] == ["text", "text", "text", "random"]
    kinds = [e["kind"] for e in plan]
    assert kinds.count("random") * 4 == len(kinds) == 116


def _five_tars_are_as_long_as_the_file_states(plan):
    stated = CONFIG["layer_tar_bytes"]
    by_dir = {layer_dir: _tar_bytes(plan, layer_dir)[0]
              for layer_dir in ("deps", "bin", "assets")}
    assert list(stated.values()) == [by_dir["deps"], by_dir["bin"],
                                     by_dir["assets"], by_dir["deps"],
                                     by_dir["bin"]]
    # The keys are the Dockerfile's five COPY lines, in its order.
    copies = [ln.split(" #!")[0] for ln in
              CONFIG["context"]["dockerfile"].splitlines() if "COPY" in ln]
    assert [k.split(": ", 1)[1] for k in stated] == copies
    for tar in stated.values():
        assert f"{tar:,}" in CONFIG["assumed"]["tar"]
        assert f"{tar % _GEAR_QUANTUM:,}" in CONFIG["assumed"]["tar"]


def _tars_sit_past_a_gear_quantum_with_room_for_the_edits(plan):
    """Every stream is under one gear block; the two that grow (1,000
    bytes a build) must not reach the next compiled shape of the scan's
    last block inside a run."""
    for layer_dir in ("assets", "deps", "bin"):
        tar, raw = _tar_bytes(plan, layer_dir)
        assert tar < _GEAR_BLOCK
        assert 1024 <= tar % _GEAR_QUANTUM <= 40 << 10
    tar, raw = _tar_bytes(plan, "bin")
    room = (_GEAR_QUANTUM - tar % _GEAR_QUANTUM + tar - raw) \
        // (2 * _BLOCK)
    # A window of 45 s and its priming made 25 edits on the chip.
    assert room >= 45
    assert f"{room} edits" in CONFIG["assumed"]["tar"]


@pytest.mark.parametrize("shape", [
    _three_directories_of_one_mebibyte, _sizes_are_the_stated_ranges,
    _every_file_of_bin_can_be_drawn_by_the_edit,
    _three_files_of_text_to_one_of_random_bytes,
    _five_tars_are_as_long_as_the_file_states,
    _tars_sit_past_a_gear_quantum_with_room_for_the_edits],
    ids=lambda f: f.__name__.strip("_"))
def test_generator_gives_the_shapes_the_file_states(shape):
    shape(gen.file_plan(CONFIG["context"]))


def _entry_in_benchmark():
    [entry] = [c for c in BENCHMARK["configs"]
               if c["name"] == "multi-stage-small"]
    assert entry["file"] == "perfbench/configs/multi-stage-small.json"
    assert entry["reduced"] == ["run_steps", "base_images"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["source"].startswith("BASELINE.json configs[1]: ")
    assert CONFIG["source"].startswith(entry["source"])
    assert _load("BASELINE.json")["configs"][1] in entry["source"]
    [cell] = [w for w in BENCHMARK["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("multi-stage-small", "edit", 1)
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in BENCHMARK["workloads"]
            if w["config"] == "multi-stage-small"] == [CELL]
    # The last of both lists at its PR; PR 38 appended after it.
    assert BENCHMARK["workloads"][6] is cell
    assert BENCHMARK["configs"][4] is entry


def _states_what_a_deployment_states():
    for key in ("source", "source_scale", "reduced_why", "assumed",
                "guarantees", "deployment", "semantics_shown"):
        assert CONFIG[key], key
    mono = _load("perfbench", "configs", "monorepo-slice.json")["guarantees"]
    assert CONFIG["guarantees"][:3] == mono
    assert "COPY --from" in CONFIG["guarantees"][3]
    assert "final stage's layers alone" in CONFIG["guarantees"][3]
    for gap in ("RUN", "base image", "symlinks", "--chown"):
        assert gap in CONFIG["assumed"]["not_generated"]
    for cut in ("run_steps", "base_images", "FROM golang", "FROM alpine"):
        assert cut in CONFIG["reduced_why"]
    assert CONFIG["build_flags"] == ["--hasher", "tpu", "--commit",
                                     "explicit", "--modifyfs"]
    assert (CONFIG["lanes"], CONFIG["templates"], CONFIG["reference"],
            CONFIG["worker"]) == (1, 0, "cdc_stages",
                                  {"max_concurrent_builds": 0})
    context = CONFIG["context"]
    assert (context["size_seed"], context["fanout"]) == (4, 7)
    assert context["sizes"] == {"lo": 4096, "hi": 65536,
                                "small_share": 0.0, "small_below": 2}


def _dockerfile_is_two_stages_and_five_commits():
    lines = CONFIG["context"]["dockerfile"].splitlines()
    assert lines == [
        "FROM scratch AS builder",
        "COPY deps /workspace/deps/ #!COMMIT",
        "COPY bin /workspace/bin/ #!COMMIT",
        "FROM scratch",
        "COPY assets /makisu-internal/certs/ #!COMMIT",
        "COPY --from=builder /workspace/deps/ /makisu-internal/lib/ #!COMMIT",
        "COPY --from=builder /workspace/bin/ /makisu-internal/bin/ #!COMMIT"]
    assert sum(ln.endswith("#!COMMIT") for ln in lines) == 5
    # Every generated directory is a layer of the image, in its order:
    # the check zips context.layers with the manifest's layers.
    stages = STAGES._parse(CONFIG["context"]["dockerfile"])
    assert [s["alias"] for s in stages] == ["builder", "1"]
    assert [dst for _, _, dst, _ in stages[1]["steps"]] \
        == [s["dest"] for s in CONFIG["context"]["layers"]]


def _cell_reports_its_metrics():
    cell = cells.Cell(os.path.join(CHECKOUT, "BENCHMARK.json"), CELL)
    assert cell.reference.__name__ == "perfbench_cdc_stages"
    # Not `stored_per_user_byte`: over six seeds it spread by 0.030 on
    # the chip, where a new cell is admitted under half the bound of
    # 0.025 (PERF.md, PR 32), so `new_chunk_bytes_share_pct`, which
    # moves it, is not this cell's either.
    assert {m["name"] for m in cell.end_to_end()} \
        == {"build_p50_s", "setup_s"}
    mine = {m["name"] for m in cell.per_layer()}
    assert set(NEW_READERS) <= mine
    assert "new_chunk_bytes_share_pct" not in mine
    assert {"sha_hbm_roofline", "gear_hbm_roofline", "apply_layer_s_per_build",
            "sync_mtime_wait_s_per_build", "chunk_probe_hit_pct",
            "idle_unspanned_pct", "compiles_in_window",
            "sink_prefetch_ready_pct", "read_wait_s_per_build"} <= mine
    # None queued for retirement, not the RSS level, none of the farm's.
    assert not mine & {"sync_wait_share_pct", "commit_share_pct",
                       "device_mb_per_build", "chunk_store_share_pct",
                       "process_rss_peak_mb", "queue_wait_p50_s",
                       "build_p90_s", "submit_retries_per_build",
                       "hash_batch_occupancy_pct"}
    for name in mine:
        assert callable(cell.reader(name))


def _new_metrics_list_their_cells():
    by_name = {m["name"]: m for m in BENCHMARK["per_layer"]}
    # Put at the end of the list at their PR, together and in order;
    # later PRs append after them.
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    first = names.index(NEW_READERS[0])
    assert names[first:first + 6] == list(NEW_READERS)
    # PR 41's cell has a RUN: it executes a COPY on disk, commits a
    # layer, waits out an mtime and ends a session, and joined those
    # five; it has one stage, and checkpoints none.
    later = ["run-steps-edit"]
    assert by_name[NEW_READERS[0]]["workloads"] == [CELL]
    for name in NEW_READERS[:3]:
        assert by_name[name]["workloads"] == [CELL] + later * (
            name != NEW_READERS[0])
        assert by_name[name]["layer"].startswith("on-disk stage tree")
    # PR 50's cell commits a layer, waits out an mtime and ends a
    # session eight at a time.
    farm = ["monorepo-farm-churn"]
    assert by_name["layer_commits_per_build"]["workloads"] \
        == [CELL, "monorepo-cold", "monorepo-edit"] + later + farm
    assert by_name["mtime_wait_slept_per_build"]["workloads"] \
        == [CELL, "monorepo-edit"] + later + farm
    assert by_name["session_finish_s_per_build"]["workloads"] == [
        CELL, "farm-churn", "farm-unchanged", "monorepo-edit",
        "small-files-edit", "farm-concurrent-churn"] + later + farm
    for name in NEW_READERS:
        assert by_name[name]["moves"] == "build_p50_s"
        assert by_name[name]["better"] == "lower"
    # Appended, never inserted: the cell was the last of every list it
    # joined, and only PR 38's, PR 41's, PR 47's and PR 50's cells have
    # been appended after it.
    for m in BENCHMARK["per_layer"][:first] \
            + BENCHMARK["per_layer"][first + 6:] + BENCHMARK["end_to_end"]:
        listed = [w for w in m.get("workloads", ())
                  if w not in ("farm-concurrent-churn", "run-steps-edit",
                               "huge-layer-pgzip-edit",
                               "monorepo-farm-churn")]
        if CELL in listed:
            assert listed[-1] == CELL, m["name"]


@pytest.mark.parametrize("statement", [
    _entry_in_benchmark, _states_what_a_deployment_states,
    _dockerfile_is_two_stages_and_five_commits, _cell_reports_its_metrics,
    _new_metrics_list_their_cells], ids=lambda f: f.__name__.strip("_"))
def test_configuration_and_cell_are_declared(statement):
    statement()


# -- (b) the same tree at 3 x 96 KiB, through a worker ---------------------


_SCALED = 96 << 10
_SMALL_EDIT = dict(EDIT["edit"], min_file_bytes=4096)


def _scaled_context():
    context = json.loads(json.dumps(CONFIG["context"]))
    for layer in context["layers"]:
        layer["bytes"] = _SCALED
    return context


def _age(tree):
    """Every file and directory of ``tree`` gets a fixed mode and an
    mtime of long ago, so that its tars are the same wherever and
    whenever they are made."""
    for parent, dirs, names in os.walk(tree, topdown=False):
        for name in names:
            os.chmod(os.path.join(parent, name), 0o644)
            os.utime(os.path.join(parent, name), (_OLD, _OLD))
        os.chmod(parent, 0o755)
        os.utime(parent, (_OLD, _OLD))


class _Worker:
    def __init__(self, work):
        self.work = work
        self.server = WorkerServer(os.path.join(work, "w.sock"),
                                   max_concurrent_builds=0)
        self.thread = self.server.serve_background()
        self.client = WorkerClient(self.server.socket_path)
        deadline = time.monotonic() + 60
        while not self.client.ready():
            assert time.monotonic() < deadline
            time.sleep(0.02)
        self.built = 0

    def counters(self):
        return stats.parse_prometheus(self.client.metrics())

    def build(self, context_dir, storage, flags):
        root = os.path.join(self.work, f"root{self.built}")
        os.makedirs(root)
        b = driver.Build(lane=0, index=self.built, kind="rebuild",
                         tag=f"multistage/t:b{self.built}",
                         context=context_dir, storage=storage,
                         context_bytes=0)
        before = self.counters()
        b.exit_code = self.client.build(
            ["--log-level", "error", "build", context_dir, "-t", b.tag,
             "--storage", storage, "--root", root] + list(flags))
        b.terminal = dict(self.client.last_build)
        events = list(self.client.last_events)
        b.spans = [(e.get("name"), e.get("duration")) for e in events
                   if e.get("type") == "span_end"]
        self.built += 1
        shutil.rmtree(root, ignore_errors=True)
        return b, events, (before, self.counters())

    def close(self):
        self.server.shutdown()
        self.server.server_close()
        self.thread.join(timeout=5)


def _digests(b):
    manifest, config, _ = check.Checker(None, {})._manifest(b)
    return ([layer["digest"] for layer in manifest["layers"]],
            config["rootfs"]["diff_ids"])


def _cache_entries(b):
    """{cache id: entry} of the layers a storage's KV names."""
    with open(os.path.join(b.storage, "cache_key_value.json"),
              encoding="utf-8") as f:
        kv = json.load(f)
    out = {}
    for key, (value, _stamp) in kv.items():
        if isinstance(value, str) and value.startswith("{"):
            entry = json.loads(value)
            if "gzip" in entry:
                out[key] = entry
    return out


def _held_to_reference(context, b):
    checker = check.Checker(STAGES, context)
    checker.check_build(b, tree_is_current=True)
    return checker


def _image_tars(b):
    """The image's layer tars, in the manifest's order, inflated."""
    manifest, _, _ = check.Checker(None, {})._manifest(b)
    out = []
    for layer in manifest["layers"]:
        hexd = layer["digest"].split(":", 1)[1]
        out.append(STAGES.inflate(
            os.path.join(b.storage, "layers", hexd[:2], hexd)))
    return out


class _Clock:
    """What ``snapshot/memfs.py`` reads the time from and sleeps on, in
    the test's hands: it stands still but for the sleeps asked of it,
    which take no time."""

    def __init__(self, now: float) -> None:
        self.now = now
        self.sleeps: list[float] = []

    def time(self) -> float:
        return self.now

    def sleep(self, seconds: float) -> None:
        self.sleeps.append(seconds)
        self.now += seconds


@contextlib.contextmanager
def _edited_a_quarter_second_ago(tree):
    """The one file of ``tree`` written since ``_age`` is stamped at the
    top of a second, and the mtime guard's clock reads a quarter of a
    second later for as long as nothing sleeps: the layer that scans
    that file waits the second out, however long the machine takes to
    reach it, and every layer after it finds the clock past it."""
    [edited] = [os.path.join(parent, name)
                for parent, _, names in os.walk(tree) for name in names
                if os.lstat(os.path.join(parent, name)).st_mtime != _OLD]
    second = int(time.time())
    os.utime(edited, (second, second))
    clock = _Clock(second + 0.25)
    real, memfs.time = memfs.time, clock
    try:
        yield clock
    finally:
        memfs.time = real


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    return _build_both(str(tmp_path_factory.mktemp("multistage")))


def _build_both(work):
    """A cold build of the scaled tree and a rebuild after one edit,
    through one worker, each held to the reference."""
    context = _scaled_context()
    out = {"context": context, "plan": gen.file_plan(context), "work": work}
    ctx = os.path.join(work, "ctx")
    gen.make_tree(context, ctx, 32)
    _age(ctx)
    storage = os.path.join(work, "storage")
    worker = _Worker(work)
    try:
        out["cold"], out["cold_events"], out["cold_counters"] = worker.build(
            ctx, storage, CONFIG["build_flags"])
        out["cold_check"] = _held_to_reference(context, out["cold"])
        out["cold_entries"] = _cache_entries(out["cold"])
        out["cold_tars"] = _image_tars(out["cold"])
        out["touched"] = gen.apply_edit(
            _SMALL_EDIT, context, ctx, np.random.default_rng([1, 0, 7]),
            "000001")
        with _edited_a_quarter_second_ago(ctx) as clock:
            out["edited"], out["edited_events"], out["edited_counters"] = \
                worker.build(ctx, storage, CONFIG["build_flags"])
        out["edited_sleeps"] = clock.sleeps
        out["edited_check"] = _held_to_reference(context, out["edited"])
        out["edited_tars"] = _image_tars(out["edited"])
    finally:
        worker.close()
    out["ctx"] = ctx
    return out


@pytest.mark.parametrize("count", sorted(check.LIMITS))
@pytest.mark.parametrize("which", ["cold", "edited"])
def test_build_held_to_the_stage_reference(built, which, count):
    assert built[which].exit_code == 0
    checker = built[which + "_check"]
    assert checker.found[count] == 0, checker.notes
    assert checker.checked["builds"] == 1 and checker.checked["layers"] == 3
    assert checker.checked["members"] == 116


def test_image_is_the_final_stages_three_layers_alone(built):
    for which in ("cold", "edited"):
        blobs, diff_ids = _digests(built[which])
        assert len(blobs) == len(diff_ids) == 3
        for tar, spec in zip(built[which + "_tars"],
                             built["context"]["layers"]):
            names = set(PLAIN.tar_members(tar))
            assert not any(n.startswith("workspace") for n in names)
            under = spec["dest"].strip("/")
            assert all(n == "makisu-internal" or n.startswith(under)
                       for n in names), spec
    # The storage's KV knows all five layers: two were the builder's.
    assert len(built["cold_entries"]) == 5
    image = set(_digests(built["cold"])[0])
    assert len({e["gzip"] for e in built["cold_entries"].values()}
               - image) == 2


def _ancestors(path):
    parts = path.split("/")[:-1]
    return ["/".join(parts[:k + 1]) for k in range(len(parts))]


def _delta(counters, name, **labels):
    before, after = counters
    return stats.counter_delta(before, after, name, **labels)


def test_cold_build_commits_five_layers_and_copies_the_builders_two(built):
    counters = built["cold_counters"]
    assert _delta(counters, COMMITS) == 5
    assert _delta(counters, REPLAY) == 0
    # The builder stage copied deps and bin under the root, and both
    # went into the sandbox for the final stage; nothing was unpacked.
    assert _delta(counters, ON_DISK, op="copy") == 2 * _SCALED
    assert _delta(counters, ON_DISK, op="checkpoint") == 2 * _SCALED
    assert _delta(counters, ON_DISK, op="untar") == 0


def test_rebuild_after_one_edit_commits_four_layers_and_unpacks_one(built):
    assert built["touched"] == 1
    counters = built["edited_counters"]
    assert _delta(counters, COMMITS) == 4
    assert _delta(counters, REPLAY, result="inflate") == 1
    assert _delta(counters, REPLAY) == 1
    # The edit landed a quarter of a second before the clock the guard
    # reads: its layer slept the rest of that second and the margin,
    # and the three after it found the second over.
    assert _delta(counters, SLEPT, result="slept") == 1
    assert _delta(counters, SLEPT) == 4
    assert built["edited_sleeps"] == [pytest.approx(0.75 + 0.02)]
    grown = _SCALED + _SMALL_EDIT["bytes"]
    assert _delta(counters, ON_DISK, op="copy") == grown
    assert _delta(counters, ON_DISK, op="untar") == _SCALED
    assert _delta(counters, ON_DISK, op="checkpoint") == _SCALED + grown
    # The one cached layer lands under an empty root: nothing is in a
    # member's way, so none is looked for before it is written.
    deps = [e["path"] for e in built["plan"] if e["layer"] == "deps"]
    dirs = {d for p in deps for d in _ancestors("workspace/" + p)}
    assert _delta(counters, UNTARRED, result="created") \
        == len(deps) + len(dirs) == 105
    assert _delta(counters, UNTARRED, result="probed") == 0
    assert _delta(built["cold_counters"], UNTARRED) == 0
    # Three of the four committed layers are blobs the storage had: the
    # final stage's cache ids follow the builder stage's seed.
    cold, edited = _digests(built["cold"])[0], _digests(built["edited"])[0]
    assert cold[:2] == edited[:2] and cold[2] != edited[2]


def _spans(events):
    """[(name, parent name, attrs at start, attrs at end)]."""
    starts = {e["span_id"]: e for e in events if e["type"] == "span_start"}
    out = []
    for e in events:
        if e["type"] != "span_end":
            continue
        start = starts[e["span_id"]]
        parent = starts.get(start.get("parent_id"), {}).get("name")
        out.append((e["name"], parent, start.get("attrs", {}),
                    e.get("attrs", {})))
    return out


def test_new_spans_open_under_their_parents_with_their_attributes(built):
    spans = _spans(built["edited_events"])
    by_name = {}
    for name, parent, at_start, at_end in spans:
        by_name.setdefault(name, []).append((parent, at_start, at_end))
    # One checkpoint and one wipe a stage, under `stage`.
    assert [(p, a["alias"], a["sources"])
            for p, a, _ in by_name["stage_checkpoint"]] \
        == [("stage", "builder", "2"), ("stage", "1", "0")]
    assert [(p, a["alias"]) for p, a, _ in by_name["stage_cleanup"]] \
        == [("stage", "builder"), ("stage", "1")]
    # One COPY ran on disk (the builder's `bin`; `deps` was cached and
    # the final stage is not copied from), under `step`.
    [(parent, _, at_end)] = by_name["copy_on_disk"]
    assert parent == "step"
    assert at_end == {"files": "4",
                      "bytes": str(_SCALED + _SMALL_EDIT["bytes"])}
    [(parent, at_start, _)] = by_name["apply_layer"]
    assert parent == "step" and at_start["untar"] == "True"
    assert len(by_name["commit_layer"]) == 4
    assert len(by_name["session_finish"]) == 1
    # The cold build copied both of the builder's directories.
    cold = [(at_end["files"], at_end["bytes"]) for name, _, _, at_end
            in _spans(built["cold_events"]) if name == "copy_on_disk"]
    assert cold == [("96", str(_SCALED)), ("4", str(_SCALED))]


def test_copied_files_keep_name_size_mode_mtime_and_bytes(built):
    """The guarantee the configuration adds, member by member: what the
    image holds under ``lib/`` and ``bin/`` is what the context holds
    under ``deps/`` and ``bin/``."""
    for tar, (sub, under) in zip(built["edited_tars"][1:],
                                 [("deps", "makisu-internal/lib"),
                                  ("bin", "makisu-internal/bin")]):
        got = {k: v for k, v in PLAIN.tar_members(tar).items()
               if v[0] == PLAIN.REGTYPE}
        assert got == PLAIN.tree_members(built["ctx"], sub, under)
        old = [v for v in got.values() if v[3] == _OLD]
        assert len(old) == len(got) - (1 if sub == "bin" else 0)
        assert {v[2] for v in got.values()} == {0o644}


# -- (c) the interpreter against the program, Dockerfile by Dockerfile -----


_DOCKERFILES = {
    "directory_to_directory": (
        "explicit",
        "FROM scratch\nCOPY a /opt/a/ #!COMMIT\nCOPY b /opt/b/ #!COMMIT\n"),
    "file_to_file": (
        "explicit", "FROM scratch\nCOPY single.txt /etc/conf.txt #!COMMIT\n"),
    "file_into_directory": (
        "explicit", "FROM scratch\nCOPY single.txt /etc/ #!COMMIT\n"),
    "copy_from_a_stage": (
        "explicit",
        "FROM scratch AS one\nCOPY a /build/a/ #!COMMIT\n"
        "FROM scratch\nCOPY --from=one /build/a/ /srv/ #!COMMIT\n"),
    "two_copies_from_one_stage": (
        "explicit",
        "FROM scratch AS one\nCOPY a /build/a/ #!COMMIT\n"
        "COPY b /build/b/ #!COMMIT\nFROM scratch\n"
        "COPY --from=one /build/b/ /srv/b/ #!COMMIT\n"
        "COPY --from=one /build/a/ /srv/a/ #!COMMIT\n"),
    "a_stage_nobody_copies_from": (
        "explicit",
        "FROM scratch AS one\nCOPY a /build/a/ #!COMMIT\n"
        "FROM scratch AS idle\nCOPY b /never/ #!COMMIT\n"
        "FROM scratch\nCOPY --from=one /build/a/ /srv/ #!COMMIT\n"),
    "one_file_from_a_stage": (
        "explicit",
        "FROM scratch AS one\nCOPY a /build/a/ #!COMMIT\n"
        "FROM scratch\nCOPY --from=one /build/a/sub/y.txt /y.copy #!COMMIT\n"),
    "implicit_commit": (
        "implicit",
        "FROM scratch AS one\nCOPY a /build/a/\n"
        "FROM scratch\nCOPY b /srv/b/\nCOPY --from=one /build/a/ /srv/a/\n"),
    "unmarked_copies_join_the_next_commit": (
        "explicit",
        "FROM scratch AS one\nCOPY a /build/a/\n"
        "FROM scratch\nCOPY b /srv/b/\nCOPY single.txt /srv/ #!COMMIT\n"
        "COPY --from=one /build/a/ /srv/a/\n"),
    "the_shipped_dockerfile": (
        "explicit",
        CONFIG["context"]["dockerfile"]),
}


def _small_tree(root):
    files = {"a/x.txt": b"x" * 3000, "a/sub/y.txt": b"y" * 70000,
             "b/z.bin": bytes(range(256)) * 20, "single.txt": b"one\n",
             "deps/d/l.so": b"lib" * 999, "bin/tool": b"\x7fELF" * 500,
             "assets/ca.pem": b"cert\n" * 40}
    for rel, body in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(body)
    os.chmod(os.path.join(root, "bin/tool"), 0o755)
    for k, (parent, _, names) in enumerate(sorted(os.walk(root))):
        for name in names:
            if name == "tool":
                continue
            os.chmod(os.path.join(parent, name), 0o640 if k % 2 else 0o644)
    stamp = _OLD
    for parent, _, names in sorted(os.walk(root), reverse=True):
        for name in sorted(names):
            stamp += 1000
            os.utime(os.path.join(parent, name), (stamp, stamp))
        os.utime(parent, (_OLD, _OLD))


@pytest.mark.parametrize("case", sorted(_DOCKERFILES))
def test_interpreter_gives_the_programs_layers(tmp_path, case):
    commit, dockerfile = _DOCKERFILES[case]
    ctx = str(tmp_path / "ctx")
    os.makedirs(ctx)
    _small_tree(ctx)
    with open(os.path.join(ctx, "Dockerfile"), "w") as f:
        f.write(dockerfile)
    storage, root = str(tmp_path / "storage"), str(tmp_path / "root")
    os.makedirs(root)
    b = driver.Build(lane=0, index=0, kind="cold", tag=f"interp/t:{case}",
                     context=ctx, storage=storage, context_bytes=0)
    b.exit_code = cli.main([
        "--log-level", "error", "build", ctx, "-t", b.tag, "--storage",
        storage, "--root", root, "--hasher", "cpu", "--modifyfs",
        "--commit", commit])
    assert b.exit_code == 0
    b.terminal = {"exit_code": 0}
    got = [{k: v for k, v in PLAIN.tar_members(tar).items()
            if v[0] == PLAIN.REGTYPE} for tar in _image_tars(b)]
    want = STAGES.image_layers(ctx, commit=commit)
    assert got == want
    assert sum(map(len, want)) >= 1
    assert not any(name.startswith(("build", "never", "workspace"))
                   for layer in got for name in layer)


@pytest.mark.parametrize("dockerfile", [
    "FROM alpine\nCOPY a /a/\n", "FROM scratch\nRUN make\n",
    "FROM scratch\nCOPY --chown=1:1 a /a/\n", "FROM scratch\nCOPY a* /a/\n",
    "FROM scratch\nCOPY a b /a/\n", "FROM scratch\nCOPY a rel/\n",
    "FROM scratch\nCOPY --from=nobody /a /a/\n", "COPY a /a/\n",
    "FROM scratch\nCOPY missing /a/\n", "FROM scratch\nWORKDIR /a\n"])
def test_interpreter_refuses_what_it_does_not_interpret(tmp_path, dockerfile):
    _small_tree(str(tmp_path))
    with open(tmp_path / "Dockerfile", "w") as f:
        f.write(dockerfile)
    with pytest.raises(ValueError):
        STAGES.image_layers(str(tmp_path))


def test_stage_reference_is_the_plain_one_plus_the_interpreter():
    with open(os.path.join(PERFBENCH, "reference", "cdc_stages.py"),
              encoding="utf-8") as f:
        source = f.read()
    assert "import makisu_tpu" not in source
    assert "from makisu_tpu" not in source
    for name in ("inflate", "sha256_hex", "file_sha256_hex", "tar_members",
                 "cut_points", "candidates", "REGTYPE", "gear_table"):
        assert getattr(STAGES, name) is getattr(STAGES._cdc, name)
    for gap in ("RUN", "WORKDIR", "--chown", "globs", "symlinks",
                "whiteouts", ".dockerignore"):
        assert gap in STAGES.__doc__


# -- (d) what the check must not let through -------------------------------


def _twin(built, name):
    """A copy of the built context (times kept) and a build record that
    points at it: the tree the check walks, to be damaged."""
    twin = os.path.join(built["work"], name)
    shutil.copytree(built["ctx"], twin, symlinks=True)
    b = driver.Build(**{**built["edited"].__dict__, "context": twin})
    return twin, b


def _some_file(tree, sub):
    return sorted(os.path.join(parent, name)
                  for parent, _, names in os.walk(os.path.join(tree, sub))
                  for name in names)[0]


def _tampered_tree(built):
    twin, b = _twin(built, "tampered")
    path = _some_file(twin, "deps")
    st = os.stat(path)
    with open(path, "r+b") as f:
        byte = f.read(1)
        f.seek(0)
        f.write(bytes([byte[0] ^ 1]))
    os.utime(path, ns=(st.st_atime_ns, st.st_mtime_ns))
    return built["context"], b, 2


def _lost_mtime(built):
    """As if ``COPY --from`` had stamped the copy with another time."""
    twin, b = _twin(built, "lost-mtime")
    os.utime(_some_file(twin, "deps"), (_OLD + 5, _OLD + 5))
    return built["context"], b, 2


def _swapped_destination(built):
    context = json.loads(json.dumps(built["context"]))
    layers = context["layers"]
    layers[0]["dest"], layers[1]["dest"] = layers[1]["dest"], \
        layers[0]["dest"]
    return context, built["edited"], 2 * (16 + 1) + 2 * (96 + 1) - 2 * 113


@pytest.mark.parametrize("damage", [
    _tampered_tree, _lost_mtime, _swapped_destination],
    ids=lambda f: f.__name__.strip("_"))
def test_check_sees_the_damage(built, damage):
    context, b, at_least = damage(built)
    checker = check.Checker(STAGES, context)
    checker.check_build(b, tree_is_current=True)
    assert checker.found["tar_members_differing"] >= max(at_least, 1)
    assert not checker.verdict()
    for count in ("cut_points_differing", "chunk_digests_differing",
                  "stored_chunks_differing", "blob_digests_differing"):
        assert checker.found[count] == 0


def test_a_builder_layer_shipped_in_the_image_would_differ(built):
    """Had the image shipped the builder's ``bin`` layer for its own:
    the same four files, under ``workspace/``."""
    image = set(_digests(built["edited"])[0])
    shipped = 0
    for entry in _cache_entries(built["edited"]).values():
        if entry["gzip"] in image:
            continue
        hexd = entry["gzip"].split(":", 1)[1]
        tar = STAGES.inflate(os.path.join(built["edited"].storage, "layers",
                                          hexd[:2], hexd))
        got = {k: v for k, v in STAGES.tar_members(tar).items()
               if v[0] == STAGES.REGTYPE}
        if not any(k.startswith("workspace/bin/") for k in got):
            continue
        want = STAGES.tree_members(built["ctx"], "bin",
                                   "/makisu-internal/bin/")
        assert {v for v in want.values() if v[3] == _OLD} \
            <= set(got.values())
        assert not set(got) & set(want)
        shipped += 1
    # The cold build's and the edited build's.
    assert shipped == 2


def test_a_builder_layer_in_the_manifest_is_a_missing_output(built):
    context = json.loads(json.dumps(built["context"]))
    context["layers"].append(dict(context["layers"][-1]))
    checker = check.Checker(STAGES, context)
    checker.check_build(built["edited"], tree_is_current=True)
    assert checker.found["missing_outputs"] == 1 and not checker.verdict()


# -- (e) the CPU hasher, and the parent's outputs --------------------------


def test_cpu_hasher_gives_the_same_layer_and_blob_digests(built):
    work = built["work"]
    root = os.path.join(work, "root-cpu")
    os.makedirs(root)
    b = driver.Build(lane=0, index=0, kind="cold", tag="multistage/t:cpu",
                     context=built["ctx"],
                     storage=os.path.join(work, "storage-cpu"),
                     context_bytes=0)
    flags = [f if f != "tpu" else "cpu" for f in CONFIG["build_flags"]]
    assert cli.main(["--log-level", "error", "build", b.context, "-t", b.tag,
                     "--storage", b.storage, "--root", root] + flags) == 0
    assert _digests(b) == _digests(built["edited"])


# What the parent commit (9d50397) gave for the cold build of the fixture's
# tree (seed 32, 3 x 96 KiB, every mode 0644/0755 and every mtime
# 1,600,000,000), recorded by running this fixture on a checkout of it
# (PR 32): cache id -> (tar digest, chunks, sha-256 of the chunk list's
# JSON, blob digest under zlib _GOLDEN_ZLIB), digests without
# their ``sha256:``. This PR adds spans and
# counters and may change none of them.
_GOLDEN_ZLIB = "1.2.13"
_GOLDEN = {
    "4824813e": (
        "b53d630a81cffa05f6712411c7e2c62e8008172dd568e90590f6ac0a56e7e208",
        18, "4b986e153390081e4f303c180582f96cf1121453f060592b6befc2af078c7e61",
        "c7f25c6e8fe0de142174bcb9b5f30667c3bf7171b19286b6eb8914f0386442bc"),
    "16275651": (
        "1cc50ac9cc451f656813b5330bca9713f03e9de66c291fd0e3ba3a304cedb287",
        12, "d215f865e278a510c84640e40337a9d09e84a7efa94ffe607e7e5aabd92ca1ea",
        "7320518d090d217a58ff85d787fa826e271afa4c0138c1452e519c3a9a540e6b"),
    "84628a6d": (
        "af449f278a2c1e0bb1150e76a69b52e20f3e6597f86d8eb8793dbe157276fce3",
        14, "a2593aa5cc134b7d7433ba76c5f30dc8b213d343ee0227882b278bf5ced9f572",
        "aad14f1ede718a36b808b80aad6b8f3583e5440fd0a0bffa55c394813d7b42fc"),
    "99bad47f": (
        "a8a125afae6d976d99ad89fda534945f9aa1d47bec6abb3ee23ab04c065786c3",
        18, "17c2cd0e3044e8380561ff921d76bcbac0bac0f2144972a5ff6a096f62e2d8db",
        "305b44e7e042aed198e60bcf918b9a9ab30712a2fce1b24abb9587ecee4b213e"),
    "457d1ed1": (
        "4969b7bc836e61ee53fe5995aaf6cd236ff50172e6af8ac9cd33a7fddd6d5f41",
        12, "2dd44305acade24815524b7cd9e08c6a23a8e1ac41a5604ccddf8412faf19fee",
        "3878d8179ea5deb567d52d6ed09a038972f7a748d4cc3cb4743abb2caa0cefc8"),
}


def test_cache_ids_tar_digests_and_chunk_lists_are_the_parents(built):
    got = {}
    for cache_id, entry in built["cold_entries"].items():
        chunks = entry["chunks"]
        got[cache_id] = (
            entry["tar"], len(chunks),
            hashlib.sha256(json.dumps(chunks).encode()).hexdigest(),
            entry["gzip"])
    assert sorted(got) == sorted(_GOLDEN)
    for cache_id, (tar, n_chunks, chunk_list, blob) in _GOLDEN.items():
        assert got[cache_id][:3] == ("sha256:" + tar, n_chunks, chunk_list)
        # The blob is zlib's; another zlib may deflate otherwise.
        if zlib.ZLIB_RUNTIME_VERSION == _GOLDEN_ZLIB:
            assert got[cache_id][3] == "sha256:" + blob, cache_id


# -- (f) the readers, on a run record made by hand -------------------------


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
    spans = [("stage_checkpoint", 0.25), ("stage_cleanup", 0.125),
             ("stage_checkpoint", 0.0625), ("stage_cleanup", 0.0625),
             ("copy_on_disk", 0.5), ("copy_on_disk", 0.25),
             ("session_finish", 2.0), ("commit_layer", 8.0)]
    r.counted = [counted(spans), counted(spans),
                 counted([("session_finish", 99.0)], ok=False)]
    r.builds = list(r.counted)
    r.counters_open = dict([
        _series(ON_DISK, 1e6, op="copy"),
        _series(ON_DISK, 2e6, op="checkpoint"),
        _series(COMMITS, 10.0),
        _series(SLEPT, 3.0, result="slept"),
        _series(SLEPT, 30.0, result="clear"),
        _series(UNTARRED, 40.0, result="created"),
        _series(UNTARRED, 0.0, result="probed")])
    r.counters_close = dict([
        _series(ON_DISK, 4e6, op="copy"),
        _series(ON_DISK, 8e6, op="checkpoint"),
        _series(ON_DISK, 3e6, op="untar"),
        _series(COMMITS, 22.0),
        _series(SLEPT, 5.0, result="slept"),
        _series(SLEPT, 40.0, result="clear"),
        _series(UNTARRED, 340.0, result="created"),
        _series(UNTARRED, 0.0, result="probed")])
    if not with_program_side:
        for b in r.counted:
            b.spans = [("apply_layer", 0.5)]
        old = dict([_series("makisu_device_h2d_bytes_total", 8.0,
                            bucket="16384")])
        r.counters_open, r.counters_close = dict(old), dict(old)
    return r


# Spans are summed over the two builds that ended well; counters grow
# over the window, over the 3 counted.
@pytest.mark.parametrize("metric,want", [
    ("stage_transition_s_per_build", 0.5),
    ("copy_on_disk_s_per_build", 0.75),
    ("on_disk_mb_per_build", (3 + 6 + 3) / 3),
    ("layer_commits_per_build", 4.0),
    ("mtime_wait_slept_per_build", 2 / 3),
    ("session_finish_s_per_build", 2.0),
    ("untar_probe_free_pct", 100.0),  # PR 42's, this cell its second
])
def test_new_reader_reads_a_run_and_nothing_from_an_older_program(
        tmp_path, metric, want):
    read = _module("readers", metric + ".py").read
    assert read(_record(tmp_path, True)) == pytest.approx(want)
    assert read(_record(tmp_path, False)) is None
    untraced = _record(tmp_path, False)
    untraced.counters_open = untraced.counters_close = None
    assert read(untraced) is None


def test_slept_reader_reads_zero_where_every_layer_was_clear(tmp_path):
    """A window in which no layer slept has no ``slept`` series yet: the
    cell still reports the metric, as 0."""
    r = _record(tmp_path, True)
    for counters in (r.counters_open, r.counters_close):
        for key in [k for k in counters if ("result", "slept") in k[1]]:
            del counters[key]
    read = _module("readers", "mtime_wait_slept_per_build.py").read
    assert read(r) == 0.0


def test_on_disk_counter_is_named_once_and_adds_once_an_operation(
        tmp_path, monkeypatch):
    assert metrics.ON_DISK_BYTES_TOTAL == ON_DISK
    from makisu_tpu.snapshot import CopyOperation, eval_symlinks
    src = tmp_path / "src"
    (src / "d").mkdir(parents=True)
    for k in range(5):
        (src / "d" / f"f{k}").write_bytes(b"z" * (100 + k))
    op = CopyOperation(["d"], str(src), "/", "/out/", internal=True)
    assert op.execute(eval_symlinks, str(tmp_path / "root")) \
        == (5, sum(100 + k for k in range(5)))
    adds = []
    monkeypatch.setattr(metrics, "counter_add",
                        lambda name, value=1.0, **labels:
                        adds.append((name, value, labels)))
    from makisu_tpu.snapshot import MemFS
    fs = MemFS(str(tmp_path / "root"), [])
    fs.checkpoint(str(tmp_path / "sandbox"), ["out"])
    assert adds == [(ON_DISK, 510, {"op": "checkpoint"})]
