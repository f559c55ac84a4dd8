"""Shared hash service: many concurrent builds, one accelerator.

A build farm node (worker mode, BASELINE config 5: 64 concurrent jobs
sharing a chip/mesh) must not let each build dispatch its own half-empty
lane batches. The service multiplexes chunk-hash requests from every
in-process ChunkSession into full fixed-shape lane batches behind a
single dispatcher thread: callers submit chunk bytes and get a Future;
the dispatcher packs whatever is pending (up to the bucket's lane count,
with a short linger for stragglers), dispatches one program, and
resolves futures on readback.

Effects: device programs stay the two compiled bucket shapes, batches
run full under concurrency, and per-build latency is bounded by the
linger (default 2ms) instead of other builds' progress.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future

import numpy as np

from makisu_tpu.chunker import route as _route
from makisu_tpu.chunker.cdc import _BUCKETS, FeedClock
from makisu_tpu.utils import metrics

# Batch-size histogram buckets: lane-fill powers of two up to the
# largest bucket's lane count.
_FILL_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0,
                 512.0)

# Occupancy histogram buckets: lanes filled ÷ lane capacity per
# dispatched program. The fleet-batching signal: a worker whose
# occupancy sits near 1.0 is amortizing device programs across builds;
# near 1/lanes it is dispatching half-empty batches and more
# concurrency (or a longer linger) would pay.
_OCCUPANCY_BUCKETS = (0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0)

# Sessions in a batch: 1 is a build riding alone.
_OWNER_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0)

class HashService:
    """Cross-build chunk-hash batcher. Thread-safe; one per process."""

    # Backpressure: per-bucket queue depth caps pending chunk BYTES at
    # ~2 full batches; faster producers block in submit() instead of
    # accumulating host memory without bound.
    QUEUE_DEPTH_BATCHES = 2

    def __init__(self, linger_seconds: float | None = None) -> None:
        if linger_seconds is None:
            # --hash-linger-ms / MAKISU_TPU_HASH_LINGER_MS (2ms
            # default); utils.concurrency owns the knob so the CLI can
            # read it without importing the device stack.
            from makisu_tpu.utils import concurrency
            linger_seconds = concurrency.hash_linger_ms() / 1000.0
        self.linger = linger_seconds
        self._queues: list[queue.Queue] = [
            queue.Queue(maxsize=lanes * self.QUEUE_DEPTH_BATCHES)
            for _, lanes in _BUCKETS]
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._dispatch_loop, args=(i,),
                             daemon=True, name=f"hashsvc-{cap}")
            for i, (cap, _) in enumerate(_BUCKETS)
        ]
        self.batches = 0  # dispatched program count (observability)
        # Batches that mixed chunks from >1 submitting session — direct
        # evidence that concurrent builds share device programs.
        self.cross_build_batches = 0
        for t in self._threads:
            t.start()

    def submit(self, data: bytes, owner=None) -> "Future[bytes]":
        """Hash one chunk; resolves to the 32-byte sha256 digest.
        ``owner`` identifies the submitting session (observability)."""
        fut: Future = Future()
        for i, (cap, _) in enumerate(_BUCKETS):
            if len(data) <= cap - 64:
                self._queues[i].put((data, fut, owner))
                return fut
        raise ValueError(f"chunk of {len(data)} bytes exceeds every bucket")

    def _dispatch_loop(self, bucket: int) -> None:
        cap, lanes = _BUCKETS[bucket]
        q = self._queues[bucket]
        while not self._stop.is_set():
            try:
                first = q.get(timeout=0.1)
            except queue.Empty:
                continue
            batch = [first]
            # Linger briefly to fill the batch from concurrent builds.
            end = self.linger
            t0 = time.monotonic()
            while len(batch) < lanes:
                remaining = end - (time.monotonic() - t0)
                if remaining <= 0:
                    break
                try:
                    batch.append(q.get(timeout=remaining))
                except queue.Empty:
                    break
            metrics.observe("makisu_hash_batch_linger_seconds",
                            time.monotonic() - t0, bucket=cap)
            metrics.gauge_set("makisu_hash_queue_depth", q.qsize(),
                              bucket=cap)
            self._run_batch(cap, lanes, batch)

    def _run_batch(self, cap: int, lanes: int, batch) -> None:
        data = np.zeros((lanes, cap), dtype=np.uint8)
        lengths = np.zeros(lanes, dtype=np.int32)
        for i, (chunk, _, _) in enumerate(batch):
            data[i, :len(chunk)] = np.frombuffer(chunk, dtype=np.uint8)
            lengths[i] = len(chunk)
        t0 = time.monotonic()
        # The builds wait in ``service_wait`` (cdc.py) while this thread
        # dispatches and reads back: the device-feed seconds and bytes
        # of the farm route are recorded here, per batch.
        clock = FeedClock()
        try:
            from makisu_tpu.ops import backend as _backend
            route = _route.chunk_route(shared=True)
            with clock.stage("sha_dispatch"):
                pending = _route.hash_lanes(route, data, lengths)
            clock.moved("h2d", "sha", data.nbytes + lengths.nbytes)
            with clock.stage("sha_readback"):
                words = _backend.sync_bounded(
                    pending, "shared-service digest readback")
            clock.moved("d2h", "sha", words.nbytes)
        except BaseException as e:  # noqa: BLE001
            clock.flush()
            metrics.counter_add("makisu_hash_batch_failures_total",
                                bucket=cap)
            for _, fut, _ in batch:
                fut.set_exception(e)
            return
        clock.flush()
        self.batches += 1
        # Device execution telemetry: dispatch latency ring + compile
        # gauge + H2D/padding-waste bytes, per bucket (ops/backend.py
        # owns the shared accounting so the lane batcher's direct
        # route exports identical series).
        _backend.note_device_dispatch(cap, lanes, len(batch),
                                      int(lengths.sum()),
                                      time.monotonic() - t0)
        owners = {owner for _, _, owner in batch if owner is not None}
        if len(owners) > 1:
            self.cross_build_batches += 1
            metrics.counter_add("makisu_hash_cross_build_batches_total")
        if owners:
            metrics.observe(metrics.HASH_BATCH_OWNERS, len(owners),
                            buckets=_OWNER_BUCKETS)
        # NOTE: the dispatcher thread runs outside any build's context,
        # so these land in the process-global registry only — correct:
        # a batch can mix several builds' chunks.
        metrics.counter_add("makisu_hash_batches_total", bucket=cap)
        metrics.counter_add("makisu_bytes_hashed_total",
                            int(lengths.sum()),
                            backend=route.sha, path="service")
        metrics.observe("makisu_hash_batch_seconds",
                        time.monotonic() - t0, bucket=cap)
        metrics.observe("makisu_hash_batch_fill", len(batch),
                        buckets=_FILL_BUCKETS, bucket=cap)
        metrics.observe("makisu_hash_batch_occupancy",
                        len(batch) / lanes,
                        buckets=_OCCUPANCY_BUCKETS, bucket=cap)
        for i, (_, fut, _) in enumerate(batch):
            fut.set_result(words[i].astype(">u4").tobytes())

    def close(self) -> None:
        """Stop dispatchers; fail any still-queued futures so no caller
        blocks forever in fut.result()."""
        self._stop.set()
        for t in self._threads:
            t.join(timeout=2)
        for q in self._queues:
            while True:
                try:
                    _, fut, _ = q.get_nowait()
                except queue.Empty:
                    break
                fut.set_exception(RuntimeError("hash service closed"))


_global_service: HashService | None = None
_global_lock = threading.Lock()


def shared_service() -> HashService:
    """Process-wide service (worker mode enables it for all builds)."""
    global _global_service
    with _global_lock:
        if _global_service is None:
            _global_service = HashService()
        return _global_service
