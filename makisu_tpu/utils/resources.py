"""Process resource sampling: RSS, CPU time, fds, I/O — attributed to
open spans.

The telemetry so far (metrics.py spans, events.py) explains where a
build's *time* went; this module explains where its *memory and CPU*
went, and — through the flight recorder — what the process looked like
right before it died. One daemon sampler thread per process:

- publishes process gauges into the global registry
  (``makisu_process_rss_bytes``, ``makisu_process_peak_rss_bytes``,
  ``makisu_process_cpu_seconds``,
  ``makisu_process_open_fds``, ``makisu_process_threads``,
  ``makisu_process_io_read_bytes`` / ``_write_bytes``) — what the
  worker's ``/metrics`` scrape sees;
- attributes each sample to the currently-open spans
  (``metrics.attribute_resource_sample``): every open span tracks its
  peak RSS, so ``makisu-tpu report`` can print it per build phase;
- on a faster beat of its own (:data:`STATE_BEAT`, working only while
  a span is open) charges what the kernel says of each thread that
  owns an open span (:class:`ThreadStates`: running, queueing for the
  interpreter lock, in a wait the program wrote, in a file-system
  call; on a CPU, runnable without one) to the thread's innermost
  open span: ``makisu_thread_state_seconds_total{span, state}``,
  ``makisu_thread_sched_seconds_total{span, kind}`` and the span's
  ``resources.cpu_seconds``. The reading is done by a native thread
  (native/threadstate.cpp); nothing runs on a building thread;
- keeps a bounded recent trajectory (:func:`trajectory`) that the
  flight recorder folds into diagnostic bundles — the "was RSS
  climbing toward the OOM?" record.

Readings come straight from ``/proc/self`` (stdlib-only, no psutil);
on hosts without procfs every field degrades to what ``os.times`` and
``resource.getrusage`` can supply rather than failing. Sampling must
never fail a build: the loop swallows per-tick errors and keeps going.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any

from makisu_tpu import native
from makisu_tpu.utils import metrics

try:
    import resource as _resource
except ImportError:  # pragma: no cover - non-POSIX
    _resource = None

DEFAULT_INTERVAL = 0.5          # seconds between samples
TRAJECTORY_KEEP = 240           # recent samples kept for bundles (~2min)
STATE_BEAT = 0.05               # seconds between thread-state beats
CALIBRATE_MS = 60               # the two helpers spin this long, once

# The columns of the native reader's table after a row's tid, by the
# counter each grows: exact from the scheduler's clocks, then sampled.
SCHED_KINDS = native.TSK_COLS[1:4]
STATES = native.TSK_COLS[4:]

_PAGE_SIZE = 4096
try:
    _PAGE_SIZE = os.sysconf("SC_PAGE_SIZE")
except (ValueError, OSError, AttributeError):  # pragma: no cover
    pass


def _rss_bytes() -> int:
    """Current resident set size. ``/proc/self/statm`` field 2 is
    resident pages; the fallback (no procfs) is ru_maxrss — a PEAK,
    but better than nothing on non-Linux dev hosts."""
    try:
        with open("/proc/self/statm", "rb") as f:
            return int(f.read().split()[1]) * _PAGE_SIZE
    except (OSError, ValueError, IndexError):
        return _peak_rss_bytes()  # pragma: no cover - non-procfs


def _peak_rss_bytes() -> int:
    """The kernel's own resident high-water mark (``ru_maxrss``, KiB on
    Linux): a peak between two samples is in it. 0 where the platform
    has no ``resource`` module."""
    if _resource is None:
        return 0
    return _resource.getrusage(_resource.RUSAGE_SELF).ru_maxrss * 1024


def _open_fds() -> int | None:
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:  # pragma: no cover - no procfs
        return None


def _proc_io() -> dict[str, int]:
    """``/proc/self/io`` read_bytes/write_bytes (actual storage I/O).
    May be absent (no procfs) or unreadable (hardened kernels)."""
    out: dict[str, int] = {}
    try:
        with open("/proc/self/io", "rb") as f:
            for line in f:
                key, _, value = line.partition(b":")
                if key in (b"read_bytes", b"write_bytes"):
                    try:
                        out[key.decode()] = int(value)
                    except ValueError:
                        pass
    except OSError:
        pass
    return out


def read_sample() -> dict[str, Any]:
    """One point-in-time resource sample (JSON-ready)."""
    times = os.times()
    sample: dict[str, Any] = {
        "ts": round(time.time(), 6),
        "rss_bytes": _rss_bytes(),
        "peak_rss_bytes": _peak_rss_bytes(),
        "cpu_seconds": round(times.user + times.system, 6),
        "threads": threading.active_count(),
    }
    fds = _open_fds()
    if fds is not None:
        sample["open_fds"] = fds
    io = _proc_io()
    if io:
        sample["io_read_bytes"] = io.get("read_bytes", 0)
        sample["io_write_bytes"] = io.get("write_bytes", 0)
    return sample


class ThreadStates:
    """Where the threads that own an open span are, charged to each
    thread's innermost open span.

    The kernel is asked by the native reader's own thread
    (``native.ThreadStateReader``), which adds to a row of shared
    memory per watched thread; :meth:`beat` reads those rows and adds
    each one's growth to the global registry's counters. It watches a
    thread from the first beat that finds a span open on it to the
    first that finds none. Without the library there is no reader and
    nothing is charged: ``makisu_thread_state_source`` reads 0."""

    def __init__(self) -> None:
        self._reader = None
        self._resolved = False
        # threading ident -> [slot, native id, the row as last read,
        # the name of the innermost span then]
        self._watched: dict[int, list] = {}

    def _resolve(self):
        """The reader, made on first need: the library loaded, the
        source probed, the interpreter lock's address found (two
        helper threads spin in pure Python while the library looks at
        what they block on: the caller is inside a foreign call and
        holds no lock), the outcome logged once."""
        if self._resolved:
            return self._reader
        self._resolved = True
        from makisu_tpu.utils import logging as log
        reader = native.thread_state_reader()
        if reader is not None and not reader.vital("sightings"):
            done = threading.Event()

            def spin() -> None:
                while not done.is_set():
                    pass
            # The helpers belong to the process, not to a build: they
            # open no span and write no log.
            # check: allow(ctx-propagation)
            helpers = [threading.Thread(target=spin, daemon=True,
                                        name=f"tsk-calibrate-{i}")
                       for i in range(2)]
            for t in helpers:
                t.start()
            try:
                reader.lib.tsk_calibrate(helpers[0].native_id,
                                         helpers[1].native_id, CALIBRATE_MS)
            finally:
                done.set()
                for t in helpers:
                    t.join()
        self._reader = reader
        source = self.publish_source()
        ref = int(reader.vital("lock_ref")) if reader is not None else 0
        log.info("thread states: source=%s lock_ref=%s beat=10ms "
                 "sightings=%d", source, hex(ref) if ref else "unset",
                 int(reader.vital("sightings")) if reader is not None else 0)
        if reader is not None:
            g = metrics.global_registry()
            # A kind the source cannot give has no series: absent, not 0.
            given = {"run": True, "runqueue": reader.vital("schedstat"),
                     "system": reader.vital("source") == 1}
            for kind in SCHED_KINDS:
                if given[kind]:
                    g.counter_add(metrics.THREAD_SCHED_SECONDS, 0.0,
                                  span="build", kind=kind)
        return reader

    def publish_source(self) -> str:
        """Set ``makisu_thread_state_source`` from what the reader last
        opened; returns the source's name."""
        reader = self._reader
        code = int(reader.vital("source")) if reader is not None else 0
        name = ("none", "stat", "syscall")[code]
        if code == 2 and not reader.vital("lock_ref"):
            code = 1
        if self._resolved:
            metrics.global_registry().gauge_set(
                metrics.THREAD_STATE_SOURCE, code)
        return name

    def beat(self) -> None:
        by_thread = metrics.open_spans_by_thread()
        if not by_thread and not self._watched:
            return
        reader = self._resolve()
        if reader is None:
            return
        g = metrics.global_registry()
        for ident, watch in list(self._watched.items()):
            slot, tid, last, name = watch
            row = reader.row(slot)
            spans = by_thread.get(ident)
            gone = row[0] != tid  # the reader freed the slot: no thread
            if gone or not spans:
                # A thread that ended took its last span's tail with it
                # (the reader's samples stop where the thread does); one
                # that lives on without a span is idle, and nobody's.
                # (Spans under a thread that is gone: its ident has a
                # new owner, watched below.)
                reader.unwatch(slot, tid)
                del self._watched[ident]
                if not gone or name is None:
                    continue
            else:
                name = watch[3] = spans[-1].name
                for s in spans:
                    if s.cpu_seconds is None:
                        s.cpu_seconds = 0.0
                spans[-1].cpu_seconds += max(row[1] - last[1], 0.0)
            watch[2] = row
            for column, now, was in zip(native.TSK_COLS[1:], row[1:],
                                        last[1:]):
                if now <= was:
                    continue
                if column in SCHED_KINDS:
                    g.counter_add(metrics.THREAD_SCHED_SECONDS, now - was,
                                  span=name, kind=column)
                else:
                    g.counter_add(metrics.THREAD_STATE_SECONDS, now - was,
                                  span=name, state=column)
        unwatched = by_thread.keys() - self._watched.keys()
        if unwatched:
            native_ids = {t.ident: t.native_id
                          for t in threading.enumerate()}
            for ident in unwatched:
                tid = native_ids.get(ident)
                slot = reader.watch(tid) if tid else -1
                if slot >= 0:
                    self._watched[ident] = [slot, tid, reader.row(slot), None]

    def release(self) -> None:
        """Unwatch everything (the native reader parks)."""
        for slot, tid, _, _ in self._watched.values():
            self._reader.unwatch(slot, tid)
        self._watched.clear()

    def vitals(self) -> dict[str, float] | None:
        reader = self._reader
        return (dict(zip(native.TSK_VITALS, reader.vitals))
                if reader is not None else None)


class ResourceSampler:
    """Background sampler; one per process (see :func:`ensure_started`).

    The trajectory deque is appended lock-free (``deque(maxlen=...)``
    appends are atomic) so the flight recorder can read it from a
    signal handler without any lock-ordering risk."""

    def __init__(self, interval: float = DEFAULT_INTERVAL) -> None:
        self.interval = max(float(interval), 0.05)
        self._trajectory: "collections.deque[dict]" = \
            collections.deque(maxlen=TRAJECTORY_KEEP)
        self._peak_rss = 0
        self.thread_states = ThreadStates()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def sample_once(self) -> dict[str, Any]:
        """Take one sample: record it, publish gauges, attribute to
        open spans. Split out of the loop so tests (and the flight
        recorder's dump path) can sample deterministically."""
        sample = read_sample()
        self._trajectory.append(sample)
        g = metrics.global_registry()
        g.gauge_set(metrics.PROCESS_RSS_BYTES, sample["rss_bytes"])
        # Never falls: the kernel's mark, or the largest sample where
        # the kernel keeps none.
        self._peak_rss = max(self._peak_rss, sample["peak_rss_bytes"],
                             sample["rss_bytes"])
        g.gauge_set(metrics.PROCESS_PEAK_RSS_BYTES, self._peak_rss)
        g.gauge_set(metrics.PROCESS_CPU_SECONDS, sample["cpu_seconds"])
        g.gauge_set(metrics.PROCESS_THREADS, sample["threads"])
        if "open_fds" in sample:
            g.gauge_set(metrics.PROCESS_OPEN_FDS, sample["open_fds"])
        if "io_read_bytes" in sample:
            g.gauge_set(metrics.PROCESS_IO_READ_BYTES,
                        sample["io_read_bytes"])
            g.gauge_set(metrics.PROCESS_IO_WRITE_BYTES,
                        sample["io_write_bytes"])
        metrics.attribute_resource_sample(sample["rss_bytes"])
        self.thread_states.publish_source()
        return sample

    def trajectory(self) -> list[dict]:
        # Race-retried, not locked: the flight recorder reads this
        # from signal handlers while the sampler thread appends.
        return metrics.snapshot_concurrent(self._trajectory)

    def _run(self) -> None:
        # The process sample rides every tenth beat at the default
        # interval; a longer interval, as many beats as fit it.
        every = max(round(self.interval / STATE_BEAT), 1)
        beats = 0
        while not self._stop.wait(STATE_BEAT):
            beats += 1
            try:
                self.thread_states.beat()
                if beats % every == 0:
                    self.sample_once()
            except Exception:  # noqa: BLE001 - sampling never fails a build
                pass

    def start(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            # Every state's series exists from here on, so a reader of
            # the counter tells 0 from absent.
            g = metrics.global_registry()
            for state in STATES:
                g.counter_add(metrics.THREAD_STATE_SECONDS, 0.0,
                              span="build", state=state)
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name="resource-sampler", daemon=True)
            self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        self.thread_states.release()


# -- process singleton ------------------------------------------------------

_sampler: ResourceSampler | None = None
_sampler_lock = threading.Lock()


def ensure_started(interval: float | None = None) -> ResourceSampler:
    """Start (or return) the process sampler. Interval resolution:
    explicit argument, then ``MAKISU_TPU_RESOURCE_INTERVAL`` seconds,
    then the 0.5s default. Idempotent — the CLI calls it on every
    invocation and a worker's many builds share one thread."""
    global _sampler
    with _sampler_lock:
        if _sampler is None:
            if interval is None:
                try:
                    interval = float(os.environ.get(
                        "MAKISU_TPU_RESOURCE_INTERVAL", "") or
                        DEFAULT_INTERVAL)
                except ValueError:
                    interval = DEFAULT_INTERVAL
            _sampler = ResourceSampler(interval)
        _sampler.start()
        return _sampler


def trajectory() -> list[dict]:
    """Recent samples (empty when the sampler never started) — the
    resource-trajectory section of diagnostic bundles. Reads the
    singleton WITHOUT ``_sampler_lock``: a signal handler may have
    interrupted ``ensure_started``/``stop`` mid-hold, and a stale
    module-global read (atomic under the GIL) is harmless here."""
    sampler = _sampler
    return sampler.trajectory() if sampler is not None else []


def thread_state_vitals() -> dict[str, float] | None:
    """The native reader's own numbers (source, the lock's address and
    its sightings, beats, reads, seconds busy), or ``None`` where the
    process has no reader (yet)."""
    sampler = _sampler
    return sampler.thread_states.vitals() if sampler is not None else None


def stop() -> None:
    """Stop the process sampler (tests)."""
    with _sampler_lock:
        if _sampler is not None:
            _sampler.stop()
