"""Run one cell once: one process that owns the chip starts a
``WorkerServer``, lanes drive it through ``WorkerClient`` over the Unix
socket, and every end-to-end number is read on the client's side.

How a window counts builds (``traffic["count"]``):

- ``started``: after set-up every lane waits for the window to open,
  then builds one build after another and reads the clock once a build,
  as the build ends. While that reading is inside the window one more
  build follows the untimed work between two builds; the build whose
  end reads past it is the last, and the check always has it. So a
  build is counted if the window was open when the one before it ended,
  and the last one runs to its end. Each build is timed by itself; no
  number depends on where the window closed.
- ``completed``: the lanes run without pause from priming on, the
  window opens once every lane has completed its rebuilds of the cell's
  kind, and a build is counted if it *completed* inside the window, with
  its whole latency from submission.

A lane's ``--root`` (``traffic["root"]``): ``build`` (or no key) names
a directory of its own every build, made before it and removed after
it, so no build meets the session the one before it left (``root`` is
in a session's identity); ``lane`` names one directory for the lane's
whole run, as a user's builds name one root every time. Nothing else
about a build differs.

``os.sync()`` is called by the harness before the window opens and,
where the mix asks for it, between builds: never inside a timed
interval (the program's own ``MemFS._sync`` flushes the whole host, so
what set-up or the previous build left dirty would be charged to
whichever build syncs next)."""

from __future__ import annotations

import dataclasses
import http.client
import os
import shutil
import socket
import threading
import time

import numpy as np

from pbharness import gen, sampler as sampler_mod, stats

_SUBMIT_RETRY_SLEEP = 0.02
_SUBMIT_RETRY_LIMIT = 1500
# What a refused or failed connect raises before the worker has seen
# the request; anything else ends the build as failed.
_CONNECT_ERRORS = (BlockingIOError, ConnectionRefusedError,
                   FileNotFoundError, socket.timeout)
_WINDOW_OPEN_MARK = "perfbench_window_open"
_REMOVE_PASSES = 3


@dataclasses.dataclass
class Build:
    lane: int
    index: int             # the lane's own count, priming included
    kind: str              # "cold" | "rebuild"
    tag: str
    context: str
    storage: str
    context_bytes: int
    t_submit: float = 0.0
    t_done: float = 0.0
    exit_code: int = -1
    terminal: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    retries: int = 0
    storage_growth: int | None = None
    counted: bool = False
    kept: bool = False     # the lane left its outputs for the check

    @property
    def seconds(self) -> float:
        return self.t_done - self.t_submit

    @property
    def ok(self) -> bool:
        return self.exit_code == 0 and bool(self.terminal)


@dataclasses.dataclass
class Run:
    """What a run hands to the readers and to the check."""
    cell: object
    seed: int
    seconds: float
    trace: bool
    work_dir: str = ""
    builds: list = dataclasses.field(default_factory=list)
    counted: list = dataclasses.field(default_factory=list)
    t_start: float = 0.0
    t_open: float = 0.0
    t_close: float = 0.0
    setup_parts: dict = dataclasses.field(default_factory=dict)
    counters_open: dict | None = None
    counters_close: dict | None = None
    samples: list | None = None
    device_trace: object = None
    probe: dict = dataclasses.field(default_factory=dict)
    compile_events: list = dataclasses.field(default_factory=list)
    device: dict = dataclasses.field(default_factory=dict)
    peaks: dict | None = None

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    @property
    def setup_s(self) -> float:
        return self.t_open - self.t_start

    def compiles_in_window(self) -> int:
        return sum(1 for t in self.compile_events
                   if self.t_open <= t <= self.t_close)


class _Lane:
    def __init__(self, run: Run, index: int, socket_path: str) -> None:
        from makisu_tpu.worker import WorkerClient
        cell = run.cell
        self.run = run
        self.index = index
        self.client = WorkerClient(socket_path)
        self.config = cell.config
        self.traffic = cell.traffic
        self.dir = os.path.join(run.work_dir, f"lane{index}")
        self.n_contexts = int(self.traffic.get("contexts_per_lane", 1))
        self.contexts = [os.path.join(self.dir, f"ctx{k}")
                         for k in range(self.n_contexts)]
        self.context_bytes = [0] * self.n_contexts
        self.rng = np.random.default_rng([run.seed, index, 7])
        self.built = 0
        self.rebuilds_done = 0
        self.storage_seen = 0
        self.measure_storage = bool(self.traffic.get("measure_storage"))
        self.fresh = bool(self.traffic.get("fresh_storage"))
        root = self.traffic.get("root", "build")
        if root not in ("build", "lane"):
            raise ValueError(f"traffic.root is {root!r}: a lane's builds "
                             "name one --root a \"build\" or one a \"lane\"")
        self.one_root = root == "lane"
        # An edit may name the leading share of the lanes it applies to.
        edit = self.traffic.get("edit") or {}
        self.edits = bool(edit) and index < max(
            1, round(float(edit.get("lanes_share", 1.0))
                     * int(self.config["lanes"])))

    def generate(self) -> None:
        templates = int(self.config.get("templates", 0))
        for k, ctx in enumerate(self.contexts):
            if templates:
                # Lanes that share a template start from equal content.
                content_seed = [self.run.seed,
                                template_of(self.index, self.config), k]
            else:
                content_seed = [self.run.seed, self.index, k]
            self.context_bytes[k] = gen.make_tree(
                self.config["context"], ctx,
                np.random.SeedSequence(content_seed).generate_state(1)[0])

    def _storage(self) -> str:
        if self.fresh:
            return os.path.join(self.dir, f"storage{self.built}")
        return os.path.join(self.dir, "storage")

    def build(self, kind: str) -> Build:
        """One build from ``client.build()`` called to its terminal
        record, resubmitted while the connect is refused."""
        slot = self.built % self.n_contexts
        if kind == "rebuild" and self.edits:
            self.context_bytes[slot] += gen.apply_edit(
                self.traffic["edit"], self.config["context"],
                self.contexts[slot], self.rng, f"{self.built:06d}")
        root = os.path.join(
            self.dir, "root" if self.one_root else f"root{self.built}")
        os.makedirs(root, exist_ok=True)
        b = Build(lane=self.index, index=self.built, kind=kind,
                  tag=f"perfbench/lane{self.index}:b{self.built}",
                  context=self.contexts[slot], storage=self._storage(),
                  context_bytes=self.context_bytes[slot])
        argv = ["--log-level", "error", "build", b.context, "-t", b.tag,
                "--storage", b.storage, "--root", root] \
            + list(self.config["build_flags"])
        b.t_submit = time.monotonic()
        while True:
            try:
                b.exit_code = self.client.build(argv)
                b.terminal = dict(self.client.last_build)
            except _CONNECT_ERRORS:
                b.retries += 1
                if b.retries < _SUBMIT_RETRY_LIMIT:
                    time.sleep(_SUBMIT_RETRY_SLEEP)
                    continue
                b.exit_code = -1
            except (OSError, RuntimeError, http.client.HTTPException):
                b.exit_code = -1
            break
        b.t_done = time.monotonic()
        b.spans = [(e.get("name"), e.get("duration"))
                   for e in self.client.last_events
                   if e.get("type") == "span_end"]
        self.built += 1
        if kind == "rebuild":
            self.rebuilds_done += 1
        if not self.one_root:
            shutil.rmtree(root, ignore_errors=True)
        if self.measure_storage:
            size = gen.tree_bytes(b.storage)
            b.storage_growth = size - (0 if self.fresh else self.storage_seen)
            self.storage_seen = size
        with _BUILDS_LOCK:
            self.run.builds.append(b)
        return b

    def after_build(self, b: Build, keep: bool) -> None:
        """Untimed: what the mix asks for between two builds. Only a
        mix of fresh storages removes one; ``b.kept`` is the record the
        check goes by."""
        b.kept = keep or not self.fresh
        if self.traffic.get("drop_sessions"):
            try:
                self.client.invalidate_sessions(b.context)
            except (OSError, RuntimeError, http.client.HTTPException):
                pass
        if not b.kept:
            # A thread of the worker that outlives the request (the
            # stat cache's deferred save) can create a file under the
            # walk, and the directory then stands: remove again.
            for _ in range(_REMOVE_PASSES):
                shutil.rmtree(b.storage, ignore_errors=True)
                if not os.path.lexists(b.storage):
                    break
        if self.traffic.get("sync_between"):
            os.sync()


_BUILDS_LOCK = threading.Lock()


def drive_started(lane, kind: str, deadline: float, keep_rng,
                  clock=time.monotonic) -> None:
    """A ``started`` lane's window: builds until one ends past the
    deadline. One clock reading a build decides both that the build is
    the last, which is always kept, and that the loop ends; of the
    others a cold mix keeps those ``keep_rng`` draws, one draw a build
    in the builds' order."""
    more = clock() < deadline
    while more:
        b = lane.build(kind)
        b.counted = True
        more = clock() < deadline
        lane.after_build(b, keep=not more or keep_rng.random() < 0.5)


def template_of(lane: int, config: dict) -> int:
    """The lane's template: lanes are dealt to ``templates`` templates
    in shares proportional to 1 / rank**zipf, the same deal for every
    seed."""
    n = int(config["templates"])
    lanes = int(config["lanes"])
    weights = np.array([1.0 / (k + 1) ** float(config.get("zipf", 1.0))
                        for k in range(n)])
    bounds = np.cumsum(weights / weights.sum()) * lanes
    return int(np.searchsorted(bounds, lane + 0.5))


def run_cell(run: Run, progress) -> None:
    """Set-up, window and drain of one run; fills ``run``. The worker
    is shut down and the lanes are joined before this returns."""
    from makisu_tpu.worker import WorkerClient, WorkerServer

    cell = run.cell
    config, traffic = cell.config, cell.traffic
    n_lanes = int(config["lanes"])
    mode = traffic["count"]
    prime_rebuilds = int(traffic.get("prime_rebuilds", 0))
    socket_path = os.path.join(run.work_dir, "worker.sock")
    parts = run.setup_parts

    t = time.monotonic()
    lanes = [_Lane(run, i, socket_path) for i in range(n_lanes)]
    for lane in lanes:
        lane.generate()
    parts["generation"] = time.monotonic() - t

    t = time.monotonic()
    server = WorkerServer(
        socket_path,
        max_concurrent_builds=int(config["worker"]["max_concurrent_builds"]))
    server.serve_background()
    control = WorkerClient(socket_path)
    deadline = time.monotonic() + 60
    while not control.ready():
        if time.monotonic() > deadline:
            raise RuntimeError("the worker never became ready")
        time.sleep(0.02)
    parts["worker_start"] = time.monotonic() - t

    open_gate = threading.Event()
    stop = threading.Event()
    primed = threading.Semaphore(0)
    errors: list[BaseException] = []
    # The cold builds kept for the check: drawn from the seed among
    # those a window can hold, and always the last one.
    keep_rng = np.random.default_rng([run.seed, 11])

    def lane_main(lane: _Lane) -> None:
        try:
            if traffic.get("warmup_cold"):
                # Untimed: warms every device shape the mix uses.
                lane.after_build(lane.build("cold"), keep=False)
            if traffic.get("prime_cold"):
                lane.build("cold")
            kind = "rebuild" if traffic.get("prime_cold") else "cold"
            if mode == "started":
                for _ in range(prime_rebuilds):
                    lane.build(kind)
                primed.release()
                open_gate.wait()
                drive_started(lane, kind, run.t_open + run.seconds,
                              keep_rng)
            else:
                signalled = prime_rebuilds == 0
                if signalled:
                    primed.release()
                while not stop.is_set():
                    lane.after_build(lane.build(kind), keep=True)
                    if not signalled and lane.rebuilds_done >= prime_rebuilds:
                        signalled = True
                        primed.release()
                if not signalled:
                    primed.release()
        except BaseException as e:  # noqa: BLE001 - re-raised by run_cell
            errors.append(e)
            primed.release()

    t = time.monotonic()
    threads = [threading.Thread(target=lane_main, args=(lane,),
                                name=f"perfbench-lane-{lane.index}")
               for lane in lanes]
    for th in threads:
        th.start()
    for _ in lanes:
        primed.acquire()
    parts["warmup_and_priming"] = time.monotonic() - t
    if errors:
        stop.set()
        open_gate.set()
        run.t_open = run.t_close = time.monotonic()

    smp = None
    trace_dir = os.path.join(run.work_dir, "trace")
    # Only a TPU's trace has a device plane to reduce (the self-check
    # runs the rest of a traced run on the CPU).
    device_trace = run.trace and run.device.get("platform") == "tpu"
    try:
        if not errors:
            t = time.monotonic()
            os.sync()
            parts["fence"] = time.monotonic() - t
            if run.trace:
                smp = sampler_mod.Sampler().start()
                time.sleep(0.5)  # the sampler's first passes are its slowest
                run.counters_open = _counters(control)
                if device_trace:
                    _start_device_trace(trace_dir)
            run.t_open = time.monotonic()
            if device_trace:
                _mark(_WINDOW_OPEN_MARK)
            progress(f"window open after {run.setup_s:.1f}s of set-up")
            open_gate.set()
            if mode == "started":
                for th in threads:
                    th.join()
                run.t_close = max([b.t_done for b in run.builds if b.counted]
                                  or [time.monotonic()])
            else:
                time.sleep(max(run.t_open + run.seconds - time.monotonic(), 0))
                run.t_close = time.monotonic()
                stop.set()
            if run.trace:
                if device_trace:
                    run.device_trace = _stop_device_trace(trace_dir, run)
                run.counters_close = _counters(control)
                smp.stop()
                run.samples = smp.window(run.t_open, run.t_close)
                smp = None
    finally:
        stop.set()
        open_gate.set()
        if smp is not None:
            smp.stop()
        for th in threads:
            th.join()
        server.shutdown()
        server.server_close()
    if errors:
        raise errors[0]
    if mode == "completed":
        count_completed(run.builds, run.t_open, run.t_close)
    run.counted = sorted((b for b in run.builds if b.counted),
                         key=lambda b: b.t_done)


def count_completed(builds, t_open: float, t_close: float) -> None:
    """The ``completed`` rule: a rebuild counts if it completed inside
    the window, wherever it was submitted."""
    for b in builds:
        b.counted = b.kind == "rebuild" and t_open <= b.t_done <= t_close


def _counters(control) -> dict:
    """The worker's counters, asked for again while the socket's
    backlog is full of lanes."""
    for _ in range(_SUBMIT_RETRY_LIMIT):
        try:
            return stats.parse_prometheus(control.metrics())
        except (OSError, http.client.HTTPException):
            time.sleep(_SUBMIT_RETRY_SLEEP)
    raise RuntimeError("the worker's /metrics never answered")


def _mark(name: str) -> None:
    import jax
    with jax.profiler.TraceAnnotation(name):
        pass


def _start_device_trace(trace_dir: str) -> None:
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # host spans come from the sampler
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def _stop_device_trace(trace_dir: str, run: Run):
    import glob
    import jax
    from pbharness import xplane
    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    return xplane.reduce(paths[0], mark=_WINDOW_OPEN_MARK,
                         window_s=run.window_s)
