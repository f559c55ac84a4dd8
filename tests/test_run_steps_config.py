"""The ``run-steps`` configuration as it is shipped
(``perfbench/configs/run-steps.json``): the generator gives the shapes
the file states; the same Dockerfile over a tree of a few dozen files
builds through a worker with the file's own flags and is held, layer by
layer and whiteouts included, to the configuration's own reference
(``perfbench/reference/cdc_run.py``, which interprets the Dockerfile and
its commands); a rebuild after one edit of ``src/`` unpacks two layers,
executes one ``RUN`` and scans the whole root; a rebuild re-executes a
``RUN`` only where a step before it changed; the reference's interpreter
agrees with the program on a set of Dockerfiles and refuses what is
outside its list; a tampered tree and a missing whiteout read as
differences; the new readers read a run record; and a worker does not
make a directory for a storage that is gone.

Needs no ``/root/reference``, no C compiler, no inotify and no root.
"""

import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
PERFBENCH = os.path.join(CHECKOUT, "perfbench")
sys.path.insert(0, PERFBENCH)

from pbharness import cells, check, driver, gen, stats  # noqa: E402

from makisu_tpu import cli  # noqa: E402
from makisu_tpu.utils import metrics  # noqa: E402
from makisu_tpu.worker import WorkerClient, WorkerServer  # noqa: E402


def _load(*parts):
    with open(os.path.join(CHECKOUT, *parts), encoding="utf-8") as f:
        return json.load(f)


def _module(*parts):
    return cells._load_module(os.path.join(PERFBENCH, *parts))


CONFIG = _load("perfbench", "configs", "run-steps.json")
BENCHMARK = _load("BENCHMARK.json")
EDIT = _load("perfbench", "traffic", "edit.json")
CELL = "run-steps-edit"
RUN = _module("reference", "cdc_run.py")
PLAIN = _module("reference", "cdc.py")
NEW_READERS = ("run_exec_s_per_build", "fs_scan_s_per_build",
               "scan_visited_per_build", "scan_whiteouts_per_build")
# The standing metrics that have something to read in this cell.
JOINED = (
    "apply_layer_s_per_build", "copy_on_disk_s_per_build",
    "on_disk_mb_per_build", "sync_os_sync_s_per_build",
    "sync_mtime_wait_s_per_build", "mtime_wait_slept_per_build",
    "layer_commits_per_build", "layer_entries_per_build",
    "tar_write_s_per_build", "copy_checksum_s_per_build",
    "layer_scan_s_per_build", "sha_hbm_roofline", "gear_hbm_roofline",
    "device_idle_pct", "idle_unspanned_pct", "compiles_in_window",
    "backend_init_s", "device_wait_s_per_build",
    "device_transfer_mb_per_build", "service_s_per_build",
    "request_overhead_s_per_build", "unspanned_s_per_build",
    "session_begin_s_per_build", "session_finish_s_per_build")
ON_DISK = "makisu_on_disk_bytes_total"
COMMITS = "makisu_layer_commits_total"
REPLAY = "makisu_layer_replay_total"
SLEPT = "makisu_mtime_wait_total"
SCANNED = "makisu_scan_entries_total"
UNTARRED = "makisu_untar_members_total"

_BLOCK, _RECORD, _GEAR_QUANTUM = 512, 10240, 64 << 10
# A time well before any test runs: no copied file waits out an mtime.
_OLD = 1_600_000_000
_DOCKERFILE = [
    "FROM scratch",
    "ENV LC_ALL=C",
    "COPY rootfs/ / #!COMMIT",
    "WORKDIR /app",
    "COPY deps/ /app/.pkgcache/",
    "RUN umask 022 && mkdir -p node_modules && cp -Rp .pkgcache/. "
    "node_modules/ && rm -rf .pkgcache #!COMMIT",
    "COPY src/ /app/src/",
    "RUN umask 022 && mkdir -p dist && cat src/*/*.js > dist/bundle.js "
    "&& rm -rf node_modules/d00 #!COMMIT"]


def _root_entries(plan):
    """Entries under the root when the last ``RUN`` has run, the root
    itself among them: what its scan visits."""
    def sub(e):
        return e["path"].split("/")[1]
    by_layer = {name: [e for e in plan if e["layer"] == name]
                for name in ("rootfs", "deps", "src")}
    kept = [e for e in by_layer["deps"] if sub(e) != "d00"]
    files = len(by_layer["rootfs"]) + len(kept) + len(by_layer["src"]) + 1
    dirs = sum(len({sub(e) for e in some})
               for some in (by_layer["rootfs"], kept, by_layer["src"])) \
        + len(("app", "app/node_modules", "app/src", "app/dist"))
    return 1 + files + dirs


# -- (a) the shapes the file states, at full size --------------------------


def _three_directories_of_the_stated_sizes(plan):
    layers = CONFIG["context"]["layers"]
    assert [(s["dir"], s["dest"], s["files"], s["bytes"], s["ext"])
            for s in layers] == [
        ("rootfs", "/", 800, 16 << 20, ".bin"),
        ("deps", "/app/.pkgcache/", 1200, 12 << 20, ".js"),
        ("src", "/app/src/", 96, 4 << 20, ".js")]
    for spec in layers:
        sizes = [e["size"] for e in plan if e["layer"] == spec["dir"]]
        assert (len(sizes), sum(sizes)) == (spec["files"], spec["bytes"])
    assert (CONFIG["files"], CONFIG["total_bytes"]) == (2096, 32 << 20)
    assert (CONFIG["run_steps"], CONFIG["commit_layers"]) == (2, 3)


def _sizes_are_the_stated_ranges(plan):
    stated = CONFIG["assumed"]["sizes"]
    for layer_dir in ("rootfs", "deps", "src"):
        sizes = [e["size"] for e in plan if e["layer"] == layer_dir]
        assert f"{min(sizes):,} to {max(sizes):,} bytes" in stated, layer_dir


def _the_edit_finds_files_of_src_to_draw(plan):
    assert CONFIG["context"]["layers"][-1]["dir"] == "src"
    assert EDIT["edit"]["layer"] == "last"
    big = [e for e in plan if e["layer"] == "src"
           and e["size"] >= EDIT["edit"]["min_file_bytes"]]
    assert len(big) >= 16
    assert f"{len(big)} of src's 96 files" in CONFIG["assumed"]["sizes"]


def _three_files_of_text_to_one_of_random_bytes(plan):
    assert CONFIG["context"]["content"] == ["text", "text", "text", "random"]
    kinds = [e["kind"] for e in plan]
    assert kinds.count("random") * 4 == len(kinds) == 2096


def _the_last_run_removes_a_directory_the_install_layer_holds(plan):
    gone = [e for e in plan if e["layer"] == "deps"
            and e["path"].split("/")[1] == "d00"]
    assert len(gone) == 33
    assert "33 files" in CONFIG["assumed"]["commands"]
    assert _root_entries(plan) == 2179
    assert "2,179 entries" in CONFIG["assumed"]["tar"]


def _the_scanned_layers_tar_has_room_for_a_windows_edits(plan):
    """The rebuilt layer (src, the bundle, the whiteout) grows by 2,000
    bytes an edit; the gear scan's last block must not reach its next
    compiled shape inside a run."""
    src = [e for e in plan if e["layer"] == "src"]
    dirs = len({e["path"].split("/")[1] for e in src}) + 4
    total = sum(e["size"] for e in src)
    raw = sum(-(-e["size"] // _BLOCK) * _BLOCK for e in src) \
        + -(-total // _BLOCK) * _BLOCK \
        + _BLOCK * (len(src) + dirs + 2) + 2 * _BLOCK
    tar = -(-raw // _RECORD) * _RECORD
    assert tar == 8488960 and f"{tar:,}" in CONFIG["assumed"]["tar"]
    room = (-(-tar // _GEAR_QUANTUM) * _GEAR_QUANTUM - raw) // (4 * _BLOCK)
    assert room >= 15
    assert f"{room} edits" in CONFIG["assumed"]["tar"]


@pytest.mark.parametrize("shape", [
    _three_directories_of_the_stated_sizes, _sizes_are_the_stated_ranges,
    _the_edit_finds_files_of_src_to_draw,
    _three_files_of_text_to_one_of_random_bytes,
    _the_last_run_removes_a_directory_the_install_layer_holds,
    _the_scanned_layers_tar_has_room_for_a_windows_edits],
    ids=lambda f: f.__name__.strip("_"))
def test_generator_gives_the_shapes_the_file_states(shape):
    shape(gen.file_plan(CONFIG["context"]))


def _entry_in_benchmark():
    [entry] = [c for c in BENCHMARK["configs"] if c["name"] == "run-steps"]
    assert entry["file"] == "perfbench/configs/run-steps.json"
    assert entry["reduced"] == CONFIG["reduced"] == [
        "files", "total_bytes", "base_images", "run_commands"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for said in ("Explicit commit and cache", "RUN npm install #!COMMIT",
                 "--modifyfs=true", "BASELINE.json configs[1]"):
        assert said in entry["source"] and said in CONFIG["source"]
    [cell] = [w for w in BENCHMARK["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("run-steps", "edit", 1)
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in BENCHMARK["workloads"]
            if w["config"] == "run-steps"] == [CELL]
    # The last of both lists at its PR.
    assert BENCHMARK["workloads"][8] is cell
    assert BENCHMARK["configs"][6] is entry


def _states_what_a_deployment_states():
    for key in ("source", "source_scale", "reduced_why", "assumed",
                "guarantees", "deployment", "cell_reports"):
        assert CONFIG[key], key
    mono = _load("perfbench", "configs", "monorepo-slice.json")["guarantees"]
    assert CONFIG["guarantees"][:3] == mono
    assert "a whiteout for each path it removed" in CONFIG["guarantees"][3]
    assert "nothing it left alone" in CONFIG["guarantees"][3]
    assert "re-executes a RUN only where" in CONFIG["guarantees"][4]
    assert "was not readable" in CONFIG["source"]
    assert "SURVEY.md:74-80" in CONFIG["source"]
    assert "SURVEY.md:178" in CONFIG["source"]
    for cut in ("files", "total_bytes", "base_images", "run_commands"):
        assert cut in CONFIG["reduced_why"]
    for gap in ("registry", "symlinks", "--chown", "USER"):
        assert gap in CONFIG["assumed"]["not_generated"]
    assert CONFIG["build_flags"] == ["--hasher", "tpu", "--commit",
                                     "explicit", "--modifyfs"]
    assert (CONFIG["lanes"], CONFIG["templates"], CONFIG["reference"],
            CONFIG["worker"]) == (1, 0, "cdc_run",
                                  {"max_concurrent_builds": 0})


def _dockerfile_is_two_runs_and_three_commits():
    lines = CONFIG["context"]["dockerfile"].splitlines()
    assert lines == _DOCKERFILE
    assert sum(ln.endswith("#!COMMIT") for ln in lines) == 3
    # The check zips context.layers with the manifest's layers: each
    # generated directory is copied once, in that order, to its `dest`.
    copies = [ln.split()[1:3] for ln in lines if ln.startswith("COPY")]
    assert copies == [[s["dir"] + "/", s["dest"]]
                      for s in CONFIG["context"]["layers"]]


def _cell_reports_its_metrics():
    cell = cells.Cell(os.path.join(CHECKOUT, "BENCHMARK.json"), CELL)
    assert cell.reference.__name__ == "perfbench_cdc_run"
    assert {m["name"] for m in cell.end_to_end()} \
        == {"build_p50_s", "setup_s"}
    mine = {m["name"] for m in cell.per_layer()}
    # PR 42's one: what the unpack under the root asks the disk; PR
    # 45's one: what a request asks about itself more than once; PR
    # 51's one: the decompress calls a cached layer's inflate takes;
    # PR 52's two that every cell reports: where the kernel says the
    # build's threads were.
    assert mine == set(NEW_READERS) | set(JOINED) | {
        "untar_probe_free_pct", "request_resolve_reuse_pct",
        "apply_inflate_reads_per_layer", "fs_blocked_s_per_build",
        "thread_state_coverage_pct"}
    for name in mine:
        assert callable(cell.reader(name))


def _new_metrics_list_their_cell():
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    assert names[55:59] == list(NEW_READERS)
    layers = {m["layer"] for m in BENCHMARK["per_layer"][:55]}
    for m in BENCHMARK["per_layer"][55:59]:
        assert m["workloads"] == [CELL]
        assert (m["moves"], m["better"]) == ("build_p50_s", "lower")
    assert BENCHMARK["per_layer"][55]["layer"] \
        == "RUN step (steps/run_step.py, shell.py)"
    for m in BENCHMARK["per_layer"][56:59]:
        assert m["layer"] in layers
    # Appended, never inserted: the cell was the last of every list it
    # joined, and only PR 47's and PR 50's cells have been appended
    # after it.
    for m in BENCHMARK["per_layer"] + BENCHMARK["end_to_end"]:
        listed = [w for w in m.get("workloads", ())
                  if w not in ("huge-layer-pgzip-edit",
                               "monorepo-farm-churn")]
        if CELL in listed:
            assert listed[-1] == CELL, m["name"]
            assert listed.count(CELL) == 1


@pytest.mark.parametrize("statement", [
    _entry_in_benchmark, _states_what_a_deployment_states,
    _dockerfile_is_two_runs_and_three_commits, _cell_reports_its_metrics,
    _new_metrics_list_their_cell], ids=lambda f: f.__name__.strip("_"))
def test_configuration_and_cell_are_declared(statement):
    statement()


@pytest.mark.parametrize("name", sorted(
    os.path.splitext(f)[0]
    for f in os.listdir(os.path.join(PERFBENCH, "configs"))))
def test_shipped_configuration_sums_to_what_it_states(name):
    """Every configuration the benchmark ships: the file parses, its
    layers add up to the files and bytes it states, the generator makes
    exactly those, and ``BENCHMARK.json`` names the file and runs it."""
    config = _load("perfbench", "configs", name + ".json")
    layers = config["context"]["layers"]
    assert config["name"] == name
    assert sum(s["bytes"] for s in layers) == config["total_bytes"]
    if "files" in config:
        assert sum(s["files"] for s in layers) == config["files"]
    plan = gen.file_plan(config["context"])
    assert sum(e["size"] for e in plan) == config["total_bytes"]
    assert len(plan) == sum(s["files"] for s in layers)
    [entry] = [c for c in BENCHMARK["configs"] if c["name"] == name]
    assert entry["file"] == f"perfbench/configs/{name}.json"
    assert any(w["config"] == name for w in BENCHMARK["workloads"])
    assert os.path.exists(os.path.join(
        PERFBENCH, "reference", config["reference"] + ".py"))


# -- (b) the same Dockerfile over a few dozen files, through a worker ------


_SCALED = {"rootfs": (40, 400_000), "deps": (60, 300_000),
           "src": (12, 200_000)}
_SMALL_EDIT = dict(EDIT["edit"], min_file_bytes=4096)


def _scaled_context():
    context = json.loads(json.dumps(CONFIG["context"]))
    for layer in context["layers"]:
        layer["files"], layer["bytes"] = _SCALED[layer["dir"]]
    return context


def _age(tree):
    """Every file and directory of ``tree`` gets a fixed mode and an
    mtime of long ago."""
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
        """One build under a fresh root, as the harness gives one."""
        root = os.path.join(self.work, f"root{self.built}")
        os.makedirs(root)
        b = driver.Build(lane=0, index=self.built, kind="rebuild",
                         tag=f"runsteps/t:b{self.built}",
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
        return {"build": b, "events": events,
                "counters": (before, self.counters())}

    def close(self):
        self.server.shutdown()
        self.server.server_close()
        self.thread.join(timeout=5)


def _manifest_digests(b):
    manifest, config, _ = check.Checker(None, {})._manifest(b)
    return ([layer["digest"] for layer in manifest["layers"]],
            config["rootfs"]["diff_ids"])


def _image_tars(b):
    """The image's layer tars, in the manifest's order, inflated."""
    out = []
    for digest in _manifest_digests(b)[0]:
        hexd = digest.split(":", 1)[1]
        out.append(RUN.inflate(
            os.path.join(b.storage, "layers", hexd[:2], hexd)))
    return out


def _held(context, result):
    checker = check.Checker(RUN, context)
    checker.check_build(result["build"], tree_is_current=True)
    result["check"] = checker
    result["tars"] = _image_tars(result["build"])
    return result


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """A cold build of the scaled tree, a rebuild after one edit of
    ``src/``, a rebuild of the tree unchanged and one after a change to
    ``deps/``, through one worker, each held to the reference while its
    tree is the tree on disk."""
    work = str(tmp_path_factory.mktemp("runsteps"))
    context = _scaled_context()
    out = {"context": context, "plan": gen.file_plan(context), "work": work}
    ctx = out["ctx"] = os.path.join(work, "ctx")
    gen.make_tree(context, ctx, 41)
    _age(ctx)
    storage = os.path.join(work, "storage")
    flags = CONFIG["build_flags"]
    worker = _Worker(work)
    try:
        out["cold"] = _held(context, worker.build(ctx, storage, flags))
        out["touched"] = gen.apply_edit(
            _SMALL_EDIT, context, ctx, np.random.default_rng([1, 0, 7]),
            "000001")
        out["edited"] = _held(context, worker.build(ctx, storage, flags))
        out["unchanged"] = _held(context, worker.build(ctx, storage, flags))
        dep = sorted(gen.layer_files(context, ctx, "deps"))[0]
        with open(dep, "ab") as f:
            f.write(b"// patched\n")
        os.utime(dep, (_OLD, _OLD))
        out["new_dep"] = _held(context, worker.build(ctx, storage, flags))
    finally:
        worker.close()
    return out


_BUILDS = ["cold", "edited", "unchanged", "new_dep"]


@pytest.mark.parametrize("count", sorted(check.LIMITS))
@pytest.mark.parametrize("which", _BUILDS)
def test_build_held_to_the_run_reference(built, which, count):
    assert built[which]["build"].exit_code == 0
    checker = built[which]["check"]
    assert checker.found[count] == 0, checker.notes
    assert check.LIMITS[count] == 0
    assert checker.checked["builds"] == 1 and checker.checked["layers"] == 3
    # rootfs; node_modules; src, the bundle and the whiteout.
    assert checker.checked["members"] == 40 + 60 + (12 + 1 + 1)


@pytest.mark.parametrize("which", _BUILDS)
def test_a_runs_layer_holds_what_the_command_changed_and_no_more(
        built, which):
    rootfs, installed, compiled = [RUN.tar_members(tar)
                                   for tar in built[which]["tars"]]
    assert all(n.split("/")[0].startswith("d") for n in rootfs)
    assert sum(v[0] == RUN.REGTYPE for v in rootfs.values()) == 40
    # The install layer: node_modules alone. The package cache it was
    # copied from was removed before the commit and no lower layer held
    # it, so it leaves neither a member nor a whiteout.
    files = {n for n, v in installed.items() if v[0] == RUN.REGTYPE}
    assert len(files) == 60
    assert all(n.startswith("app/node_modules/d") for n in files)
    assert not any(".pkgcache" in n or ".wh." in n for n in installed)
    # The compile layer: src as copied, the bundle, and one whiteout
    # for the directory of node_modules the command removed; nothing of
    # rootfs or of the rest of node_modules, which it left alone.
    files = {n for n, v in compiled.items() if v[0] == RUN.REGTYPE}
    assert {n for n in files if not n.startswith("app/src/")} \
        == {"app/dist/bundle.js", "app/node_modules/.wh.d00"}
    assert len(files) == 12 + 2
    assert compiled["app/node_modules/.wh.d00"][1] == 0
    assert any(n.startswith("app/node_modules/d00/") for n in installed)


def test_the_bundle_is_srcs_files_in_byte_order(built):
    want = b""
    ctx = built["ctx"]
    for path in sorted(gen.layer_files(built["context"], ctx, "src"),
                       key=lambda p: os.path.relpath(p, ctx).encode()):
        with open(path, "rb") as f:
            want += f.read()
    compiled = RUN.tar_members(built["new_dep"]["tars"][2])
    kind, size, mode, mtime, digest = compiled["app/dist/bundle.js"]
    assert (size, mode, digest) == (len(want), 0o644, RUN.sha256_hex(want))
    # No stated time for a command's output; the tar has a fresh one.
    assert mtime is None
    plain = PLAIN.tar_members(built["new_dep"]["tars"][2])
    assert plain["app/dist/bundle.js"][3] > _OLD


def _delta(one, name, **labels):
    before, after = one["counters"]
    return stats.counter_delta(before, after, name, **labels)


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


def _named(result, name):
    return [(parent, at_start, at_end) for n, parent, at_start, at_end
            in _spans(result["events"]) if n == name]


def test_cold_build_executes_both_runs_and_scans_the_root_twice(built):
    cold = built["cold"]
    assert _delta(cold, COMMITS) == 3
    assert _delta(cold, REPLAY) == 0
    assert _delta(cold, ON_DISK, op="copy") == 900_000
    assert _delta(cold, ON_DISK, op="untar") == 0
    assert [(p, e) for p, _, e in _named(cold, "run_exec")] \
        == [("step", {"exit": "0"})] * 2
    scans = _named(cold, "layer_scan")
    assert [(p, s["kind"]) for p, s, _ in scans] == [
        ("commit_layer", "copy_ops"), ("commit_layer", "scan"),
        ("commit_layer", "scan")]
    assert "visited" not in scans[0][2]
    # After the install: the root, rootfs' 40 files in 37 directories,
    # app, node_modules, its 60 files in 37 directories.
    assert int(scans[1][2]["visited"]) == 1 + 40 + 37 + 2 + 60 + 37
    assert int(scans[2][2]["visited"]) == _root_entries(built["plan"])
    assert _delta(cold, SCANNED, result="visited") \
        == sum(int(e["visited"]) for _, _, e in scans[1:])
    assert _delta(cold, SCANNED, result="whiteout") == 1
    # Every entry a scan added, ancestors written again among them.
    assert _delta(cold, SCANNED, result="added") + 1 \
        == sum(int(e["entries"]) for _, _, e in scans[1:])


def test_rebuild_after_one_edit_unpacks_two_layers_and_executes_one_run(
        built):
    assert built["touched"] == 1
    edited = built["edited"]
    assert _delta(edited, COMMITS) == 1
    assert _delta(edited, REPLAY, result="inflate") == 2
    assert _delta(edited, REPLAY) == 2
    assert [(p, s["untar"]) for p, s, _ in _named(edited, "apply_layer")] \
        == [("step", "True")] * 2
    assert _delta(edited, ON_DISK, op="untar") == 700_000
    # Under the fresh root a member's first write makes it: rootfs' 40
    # files and 37 directories, node_modules, its 60 files and 37
    # directories; only `app`, which WORKDIR made, was in a member's way.
    assert _delta(edited, UNTARRED, result="created") == 77 + 98
    assert _delta(edited, UNTARRED, result="probed") == 1
    assert _delta(built["cold"], UNTARRED) == 0
    assert _delta(edited, ON_DISK, op="copy") \
        == 200_000 + _SMALL_EDIT["bytes"]
    [(parent, _, at_end)] = _named(edited, "run_exec")
    assert (parent, at_end) == ("step", {"exit": "0"})
    [(parent, at_start, at_end)] = _named(edited, "copy_on_disk")
    assert parent == "step" and at_end["files"] == "12"
    # The whole root is walked for the one layer: every entry of it
    # visited, 12 files of src, the bundle and their directories added.
    [(parent, at_start, at_end)] = _named(edited, "layer_scan")
    assert (parent, at_start["kind"]) == ("commit_layer", "scan")
    assert int(at_end["visited"]) == _root_entries(built["plan"]) == 202
    assert _delta(edited, SCANNED, result="visited") == 202
    assert _delta(edited, SCANNED, result="whiteout") == 1
    assert _delta(edited, SCANNED, result="added") \
        == int(at_end["entries"]) - 1 == 12 + 12 + 1 + 4
    # The bundle was written an instant before the scan: the layer
    # slept the second out, or a slow machine found it over (the sleep
    # itself is tests/test_mtime_wait.py's).
    assert _delta(edited, SLEPT) == 1
    # rootfs and the install layer are the blobs the cold build stored.
    cold, now = (_manifest_digests(built[k]["build"])[0]
                 for k in ("cold", "edited"))
    assert cold[:2] == now[:2] and cold[2] != now[2]


def test_rebuild_of_an_unchanged_tree_executes_no_run(built):
    unchanged = built["unchanged"]
    assert _named(unchanged, "run_exec") == []
    assert _named(unchanged, "layer_scan") == []
    assert _delta(unchanged, COMMITS) == 0
    assert _delta(unchanged, SCANNED) == 0
    assert _manifest_digests(unchanged["build"]) \
        == _manifest_digests(built["edited"]["build"])


def test_a_changed_dependency_executes_both_runs_again(built):
    """A rebuild re-executes a ``RUN`` where a step before it in the
    chain changed: the install's ``COPY`` did, so both run; rootfs, before
    it, is a cache hit."""
    new_dep = built["new_dep"]
    assert len(_named(new_dep, "run_exec")) == 2
    assert _delta(new_dep, COMMITS) == 2
    assert _delta(new_dep, REPLAY, result="inflate") == 1
    before, now = (_manifest_digests(built[k]["build"])[0]
                   for k in ("unchanged", "new_dep"))
    assert before[0] == now[0] and before[1] != now[1]


def test_cpu_hasher_gives_the_same_members(built):
    """Layer digests cannot be compared (the bundle's time is the
    build's), the members can."""
    work = built["work"]
    root = os.path.join(work, "root-cpu")
    os.makedirs(root)
    b = driver.Build(lane=0, index=0, kind="cold", tag="runsteps/t:cpu",
                     context=built["ctx"],
                     storage=os.path.join(work, "storage-cpu"),
                     context_bytes=0)
    flags = [f if f != "tpu" else "cpu" for f in CONFIG["build_flags"]]
    assert cli.main(["--log-level", "error", "build", b.context, "-t", b.tag,
                     "--storage", b.storage, "--root", root] + flags) == 0
    def members(tars):
        return [{k: v for k, v in RUN.tar_members(tar).items()
                 if v[0] == RUN.REGTYPE} for tar in tars]
    assert members(_image_tars(b)) == members(built["new_dep"]["tars"])


# -- (c) the interpreter against the program, Dockerfile by Dockerfile -----


_HEAD = "FROM scratch\nENV LC_ALL=C\nWORKDIR /w\n"
_DOCKERFILES = {
    "the_shipped_dockerfile": CONFIG["context"]["dockerfile"],
    "a_removed_file_leaves_a_whiteout": (
        _HEAD + "COPY a/ /w/a/ #!COMMIT\n"
        "RUN rm -rf a/x.js #!COMMIT\n"),
    "removed_before_its_commit_leaves_nothing": (
        _HEAD + "COPY a/ /w/a/\nRUN rm -rf a/sub #!COMMIT\n"
        "COPY b/ /w/b/ #!COMMIT\n"),
    "a_run_without_commit_joins_the_next_layer": (
        _HEAD + "COPY a/ /w/a/ #!COMMIT\n"
        "RUN umask 022 && mkdir -p dist && cat a/sub/*.js > dist/o.js\n"
        "COPY b/ /w/b/ #!COMMIT\n"),
    "a_copy_of_a_tree_keeps_times_and_modes": (
        _HEAD + "COPY a/ /w/a/ #!COMMIT\n"
        "RUN umask 022 && mkdir -p c/d && cp -Rp a/. c/d/ #!COMMIT\n"),
    "a_whole_tree_removed_is_one_whiteout": (
        _HEAD + "COPY a/ /w/a/\nCOPY b/ /w/b/ #!COMMIT\n"
        "RUN rm -rf a && rm -rf nothing-there #!COMMIT\n"),
}


def _small_tree(root):
    files = {"a/x.js": b"x" * 3000, "a/y.js": b"why\n" * 700,
             "a/sub/z.js": b"z" * 70000, "a/sub/.hidden.js": b"h" * 10,
             "b/tool": b"\x7fELF" * 500, "b/readme": b"read me\n",
             "rootfs/d00/f.bin": b"r" * 999, "deps/d00/m.js": b"m" * 2000,
             "deps/d01/n.js": b"n" * 2100, "src/d02/s.js": b"s" * 5000,
             "src/d03/t.js": b"t" * 4000}
    for rel, body in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(body)
    stamp = _OLD
    for parent, _, names in sorted(os.walk(root), reverse=True):
        for k, name in enumerate(sorted(names)):
            stamp += 1000
            os.chmod(os.path.join(parent, name), 0o640 if k % 2 else 0o644)
            os.utime(os.path.join(parent, name), (stamp, stamp))
        os.utime(parent, (_OLD, _OLD))
    os.chmod(os.path.join(root, "b/tool"), 0o755)


@pytest.mark.parametrize("case", sorted(_DOCKERFILES))
def test_interpreter_gives_the_programs_layers(tmp_path, case):
    ctx = str(tmp_path / "ctx")
    os.makedirs(ctx)
    _small_tree(ctx)
    with open(os.path.join(ctx, "Dockerfile"), "w") as f:
        f.write(_DOCKERFILES[case])
    storage, root = str(tmp_path / "storage"), str(tmp_path / "root")
    os.makedirs(root)
    b = driver.Build(lane=0, index=0, kind="cold", tag=f"interp/t:{case}",
                     context=ctx, storage=storage, context_bytes=0)
    b.exit_code = cli.main([
        "--log-level", "error", "build", ctx, "-t", b.tag, "--storage",
        storage, "--root", root, "--hasher", "cpu", "--modifyfs",
        "--commit", "explicit"])
    assert b.exit_code == 0
    b.terminal = {"exit_code": 0}
    got = [{k: v for k, v in RUN.tar_members(tar).items()
            if v[0] == RUN.REGTYPE} for tar in _image_tars(b)]
    want = RUN.image_layers(ctx)
    assert got == want
    assert sum(map(len, want)) >= 1


@pytest.mark.parametrize("dockerfile", [
    "FROM alpine\nCOPY a/ /a/\n",
    "FROM scratch AS one\nCOPY a/ /a/\n",
    "FROM scratch\nADD a/ /a/\n",
    "FROM scratch\nUSER nobody\n",
    "FROM scratch\nCOPY --chown=1:1 a/ /a/\n",
    "FROM scratch\nCOPY a/ rel/\n",
    "FROM scratch\nCOPY missing/ /a/\n",
    "FROM scratch\nCOPY a/ /a/\nRUN make\n",
    "FROM scratch\nCOPY a/ /a/\nRUN rm -rf /a\n",
    "FROM scratch\nCOPY a/ /a/\nRUN rm -rf ../a\n",
    "FROM scratch\nCOPY a/ /a/\nRUN rm -r a\n",
    "FROM scratch\nCOPY a/ /a/\nRUN rm -rf a; true\n",
    "FROM scratch\nCOPY a/ /a/\nRUN rm -rf a || true\n",
    "FROM scratch\nCOPY a/ /a/\nRUN rm -rf $HOME\n",
    "FROM scratch\nCOPY a/ /a/\nRUN mkdir -p d\n",
    "FROM scratch\nCOPY a/ /a/\nRUN umask 077 && mkdir -p d\n",
    "FROM scratch\nCOPY a/ /a/\nRUN umask 022 && mkdir d\n",
    "FROM scratch\nCOPY a/ /a/\nRUN cp -R a/. b/\n",
    "FROM scratch\nCOPY a/ /a/\nRUN umask 022 && cp -Rp a/. missing/\n",
    # A glob's order is the shell's only under ENV LC_ALL=C.
    "FROM scratch\nCOPY a/ /a/\n"
    "RUN umask 022 && mkdir -p dist && cat a/*.js > dist/o.js\n",
    # Only `cat > F` writes under dist/, and nowhere else.
    "FROM scratch\nENV LC_ALL=C\nCOPY a/ /a/\n"
    "RUN umask 022 && mkdir -p out && cat a/*.js > out/o.js\n",
    "FROM scratch\nENV LC_ALL=C\nCOPY a/ /dist/\n",
    "FROM scratch\nENV LC_ALL=C\nCOPY a/ /a/\n"
    "RUN umask 022 && cat a/*.js > dist/o.js\n",
    "FROM scratch\nENV LC_ALL=C\nCOPY a/ /a/\n"
    "RUN umask 022 && mkdir -p dist && cat a/*.js >> dist/o.js\n",
    "FROM scratch\nENV LC_ALL=C\nCOPY a/ /a/\n"
    "RUN umask 022 && mkdir -p dist && cat a/*.none > dist/o.js\n",
    "FROM scratch\nENV LC_ALL=C\nCOPY a/ /a/\n"
    "RUN umask 022 && mkdir -p dist && cat a/*.js | sort > dist/o.js\n",
    "FROM scratch\nCOPY a/ /a/ # why\n",
    "FROM scratch\nCOPY a/ \\\n /a/\n",
    "COPY a/ /a/\n"])
def test_interpreter_refuses_what_it_does_not_interpret(tmp_path, dockerfile):
    _small_tree(str(tmp_path))
    with open(tmp_path / "Dockerfile", "w") as f:
        f.write(dockerfile)
    with pytest.raises(ValueError):
        RUN.image_layers(str(tmp_path))


def test_run_reference_is_the_plain_one_plus_the_interpreter():
    with open(os.path.join(PERFBENCH, "reference", "cdc_run.py"),
              encoding="utf-8") as f:
        source = f.read()
    assert "import makisu_tpu" not in source
    assert "from makisu_tpu" not in source
    for name in ("inflate", "sha256_hex", "file_sha256_hex", "cut_points",
                 "candidates", "REGTYPE", "gear_table"):
        assert getattr(RUN, name) is getattr(RUN._cdc, name)
    for said in ("no stated time", "directory named ``dist``", "whiteout",
                 "umask 022", "LC_ALL=C", "symlinks", ".dockerignore"):
        assert said in RUN.__doc__
    # The one place the two differ: times and whiteouts' modes left out.
    tar = b"\0" * 1024
    assert RUN.tar_members(tar) == PLAIN.tar_members(tar) == {}


# -- (d) what the check must not let through -------------------------------


def _twin(built, name):
    """A copy of the built context (times kept) and a build record that
    points at it: the tree the check walks, to be damaged."""
    twin = os.path.join(built["work"], name)
    shutil.copytree(built["ctx"], twin, symlinks=True)
    b = driver.Build(**{**built["new_dep"]["build"].__dict__,
                        "context": twin})
    return twin, b


def _some_file(tree, sub):
    return sorted(os.path.join(parent, name)
                  for parent, _, names in os.walk(os.path.join(tree, sub))
                  for name in names)[-1]


def _tampered_dependency(built):
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
    """As if ``cp -p`` had not kept a dependency's time."""
    twin, b = _twin(built, "lost-mtime")
    os.utime(_some_file(twin, "deps"), (_OLD + 5, _OLD + 5))
    return built["context"], b, 2


def _tampered_source_shows_in_the_bundle_too(built):
    twin, b = _twin(built, "tampered-src")
    path = _some_file(twin, "src")
    st = os.stat(path)
    with open(path, "r+b") as f:
        f.write(b"?")
    os.utime(path, ns=(st.st_atime_ns, st.st_mtime_ns))
    return built["context"], b, 4


def _a_whiteout_the_dockerfile_does_not_ask_for(built):
    """Held to a Dockerfile whose last ``RUN`` removes nothing: the
    image's whiteout is one member too many."""
    twin, b = _twin(built, "no-prune")
    with open(os.path.join(twin, "Dockerfile"), "w") as f:
        f.write(CONFIG["context"]["dockerfile"].replace(
            " && rm -rf node_modules/d00", ""))
    return built["context"], b, 1


def _a_whiteout_that_is_missing(built):
    """Held to a Dockerfile whose last ``RUN`` removes one directory
    more: the image lacks its whiteout."""
    twin, b = _twin(built, "more-pruned")
    with open(os.path.join(twin, "Dockerfile"), "w") as f:
        f.write(CONFIG["context"]["dockerfile"].replace(
            "rm -rf node_modules/d00",
            "rm -rf node_modules/d00 && rm -rf node_modules/d01"))
    return built["context"], b, 1


def _swapped_destination(built):
    context = json.loads(json.dumps(built["context"]))
    layers = context["layers"]
    layers[1]["dest"], layers[2]["dest"] = layers[2]["dest"], \
        layers[1]["dest"]
    return context, built["new_dep"]["build"], 60 + 14 + 2


@pytest.mark.parametrize("damage", [
    _tampered_dependency, _lost_mtime,
    _tampered_source_shows_in_the_bundle_too,
    _a_whiteout_the_dockerfile_does_not_ask_for,
    _a_whiteout_that_is_missing, _swapped_destination],
    ids=lambda f: f.__name__.strip("_"))
def test_check_sees_the_damage(built, damage):
    context, b, at_least = damage(built)
    checker = check.Checker(RUN, context)
    checker.check_build(b, tree_is_current=True)
    assert checker.found["tar_members_differing"] >= at_least
    assert not checker.verdict()
    for count in ("cut_points_differing", "chunk_digests_differing",
                  "stored_chunks_differing", "blob_digests_differing"):
        assert checker.found[count] == 0


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
    # A COPY's layer, then a RUN's: spans close children first.
    spans = [("layer_scan", 4.0), ("commit_layer", 5.0),
             ("run_exec", 0.25), ("run_exec", 0.5),
             ("layer_scan", 1.5), ("commit_layer", 3.0),
             ("layer_scan", 8.0), ("commit_layer", 9.0)]
    r.counted = [counted(spans), counted(spans),
                 counted([("run_exec", 99.0), ("layer_scan", 99.0)],
                         ok=False)]
    r.builds = list(r.counted)
    r.counters_open = dict([
        _series(SCANNED, 100.0, result="visited"),
        _series(SCANNED, 10.0, result="added"),
        _series(SCANNED, 0.0, result="whiteout"),
        _series(UNTARRED, 1000.0, result="created"),
        _series(UNTARRED, 50.0, result="probed")])
    r.counters_close = dict([
        _series(SCANNED, 6400.0, result="visited"),
        _series(SCANNED, 310.0, result="added"),
        _series(SCANNED, 6.0, result="whiteout"),
        _series(UNTARRED, 7300.0, result="created"),
        _series(UNTARRED, 60.0, result="probed")])
    if not with_program_side:
        for b in r.counted:
            b.spans = [("layer_scan", 0.5), ("commit_layer", 1.0)]
        old = dict([_series("makisu_layer_entries_total", 8.0, kind="file")])
        r.counters_open, r.counters_close = dict(old), dict(old)
    return r


# Spans are summed over the two builds that ended well; counters grow
# over the window, over the 3 counted.
@pytest.mark.parametrize("metric,want", [
    ("run_exec_s_per_build", 0.75),
    ("fs_scan_s_per_build", 1.5),
    ("scan_visited_per_build", 2100.0),
    ("scan_whiteouts_per_build", 2.0),
    ("untar_probe_free_pct", 100 * 6300 / 6310),
])
def test_new_reader_reads_a_run_and_nothing_from_an_older_program(
        tmp_path, metric, want):
    read = _module("readers", metric + ".py").read
    assert read(_record(tmp_path, True)) == pytest.approx(want)
    assert read(_record(tmp_path, False)) is None
    untraced = _record(tmp_path, False)
    untraced.counters_open = untraced.counters_close = None
    assert read(untraced) is None


def test_whiteout_reader_reads_zero_where_no_scan_found_one(tmp_path):
    r = _record(tmp_path, True)
    for counters in (r.counters_open, r.counters_close):
        for key in [k for k in counters if ("result", "whiteout") in k[1]]:
            del counters[key]
    read = _module("readers", "scan_whiteouts_per_build.py").read
    assert read(r) == 0.0


def test_probe_free_reader_reads_nothing_where_nothing_was_unpacked(
        tmp_path):
    """A window whose builds unpacked no layer on disk has the series
    and no growth: no share to give."""
    r = _record(tmp_path, True)
    r.counters_close.update(
        {k: v for k, v in r.counters_open.items() if k[0] == UNTARRED})
    assert _module("readers", "untar_probe_free_pct.py").read(r) is None


def test_probe_free_metric_lists_the_two_cells_that_unpack_on_disk():
    assert metrics.UNTAR_MEMBERS_TOTAL == UNTARRED
    [m] = [m for m in BENCHMARK["per_layer"]
           if m["name"] == "untar_probe_free_pct"]
    assert m is BENCHMARK["per_layer"][59]  # appended at PR 42
    on_disk = {x["name"]: x for x in BENCHMARK["per_layer"]}[
        "on_disk_mb_per_build"]
    assert m == {"name": "untar_probe_free_pct", "unit": "%",
                 "better": "higher", "source": "program_counter",
                 "layer": on_disk["layer"], "moves": "build_p50_s",
                 "workloads": ["multi-stage-small-edit", CELL]}
    # The cells with --modifyfs: no other unpacks a layer under a root.
    assert m["workloads"] == on_disk["workloads"]


def test_scan_counter_is_named_once_and_adds_once_a_result_a_layer(
        tmp_path, monkeypatch):
    import io
    import tarfile
    from makisu_tpu.snapshot import MemFS
    assert metrics.SCAN_ENTRIES_TOTAL == SCANNED
    root = tmp_path / "root"
    (root / "d").mkdir(parents=True)
    for k in range(5):
        (root / "d" / f"f{k}").write_bytes(b"z" * (100 + k))
    fs = MemFS(str(root), [], sync_wait=0.0)
    adds = []
    monkeypatch.setattr(metrics, "counter_add",
                        lambda name, value=1.0, **labels:
                        adds.append((name, value, labels)))

    def scan():
        del adds[:]
        with tarfile.open(fileobj=io.BytesIO(), mode="w|") as tw:
            fs.add_layer_by_scan(tw)
        return [(v, labels["result"]) for n, v, labels in adds
                if n == SCANNED]
    assert scan() == [(7, "visited"), (6, "added"), (0, "whiteout")]
    shutil.rmtree(root / "d")
    (root / "new").write_bytes(b"n")
    assert scan() == [(2, "visited"), (1, "added"), (1, "whiteout")]


# -- (f) a storage that is gone stays gone ---------------------------------


def test_worker_makes_no_directory_for_a_storage_that_is_gone(
        tmp_path, monkeypatch):
    """The request's tear-down (the tenant's attribution sidecar, the
    eviction pass) runs after the build: where the client has removed
    the storage by then, a CI job's scratch volume, the worker leaves it
    removed."""
    storage, ctx = tmp_path / "storage", tmp_path / "ctx"
    ctx.mkdir()
    (ctx / "Dockerfile").write_text("FROM scratch\n")
    monkeypatch.setenv("MAKISU_TPU_STORAGE_BUDGET_MB", "1")
    server = WorkerServer(str(tmp_path / "w.sock"), max_concurrent_builds=0)
    argv = ["build", str(ctx), "-t", "gone/t:1", "--storage", str(storage),
            "--root", str(tmp_path / "root")]
    kept = []

    def build_then_lose_the_storage(_argv, _args=None):
        storage.mkdir()
        (storage / "layers").mkdir()
        if not kept:
            shutil.rmtree(storage)
        return 0
    monkeypatch.setattr(cli, "main", build_then_lose_the_storage)

    def request():
        record = server.register_build(argv, "team-a")
        record.layer_hexes = lambda: ["ab" * 32]
        assert server.run_build(argv, lambda line: None, record) == 0
    try:
        request()
        assert not storage.exists()
        # With the storage in place the same tear-down writes into it.
        kept.append(True)
        request()
        assert "attribution.json" in os.listdir(storage)
    finally:
        server.server_close()
