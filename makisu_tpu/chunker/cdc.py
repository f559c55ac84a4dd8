"""Streaming content-defined chunking + lane-parallel chunk hashing.

A ``ChunkSession`` consumes an arbitrarily long byte stream in fixed-size
blocks and produces content-defined chunks with SHA-256 fingerprints:

1. Each block ships to the accelerator once; ``ops.gear.gear_bitmap``
   returns a bit-packed candidate-boundary bitmap (3% readback). Blocks
   after the first carry a ``WINDOW``-byte halo from the previous block so
   per-position hashes are identical to one continuous stream.
2. A greedy host pass applies min/max chunk-size policy to the candidate
   positions (cheap: a few comparisons per candidate, not per byte).
3. Chunk bytes batch into fixed-shape lane buffers — bucketed capacities
   so XLA compiles one program per bucket, never per input — and hash in
   lock-step on the VPU (``ops.sha256.sha256_lanes``).

Everything dispatches asynchronously; device→host syncs happen only for
bitmap readback and at ``finish()``.

Failure discipline: a device-plane failure fails the build. A probe
that cannot bring the backend up (bounded and process-cached,
ops/backend.py: an init that hangs never raises), a kernel the compiler
refuses, an OOM, a lost device or a readback that times out all raise
out of the session with their reason, and ``cli.main`` turns that into
exit 1 — in the CLI and in a worker alike. The route a session takes
(chunker/route.py) is decided once and never switched under it: a
build that finished is a build whose every byte went the way its
counters say. Chunk fingerprints are an optimization (the layer's
registry identity comes from the CPU digests), so an operator who
prefers a finished build to a complete one sets
``MAKISU_TPU_CHUNK_STRICT=0``: the session then degrades — the layer
commits with an empty chunk list and whole-layer caching only, with a
warning.

This is the long-stream scaling design the reference lacks (its hashing is
a single sequential SHA-256 stream, lib/builder/step/common.go:35-67); see
SURVEY.md §5 "long-context" mapping.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import time
import typing

import jax
import numpy as np

from makisu_tpu.chunker import route as _route
from makisu_tpu.ops import backend as _backend
from makisu_tpu.ops import gear
from makisu_tpu.utils import concurrency, metrics

BLOCK = 4 * 1024 * 1024  # bytes shipped to the device per gear dispatch

# Chunk bytes accumulated before one pooled SHA task dispatches. Sized
# for GIL economics, not just task overhead: a pooled task is ONE
# GIL-released native call (native.sha256_batch), and each task costs
# ~2 GIL acquisitions (entry + return) that can each wait a full
# 5ms switch interval behind the GIL-bound producer thread — so
# batches must be big enough that hashing time dwarfs handoff time.
SHA_BATCH_BYTES = 1024 * 1024

# makisu_chunk_size_bytes histogram ladder: powers of two around the
# 8KiB average / 64KiB max chunk policy (gear.DEFAULT_*).
CHUNK_SIZE_BUCKETS = (1024.0, 2048.0, 4096.0, 8192.0, 16384.0,
                      32768.0, 65536.0, 131072.0)

# Fingerprint observer: the chunk-dedup cache registers a callback per
# build (cache/chunks.attach_chunk_dedup) and CAS-existence lookups
# issue as fingerprints stream out of the hash stage, instead of as a
# serial stat storm after finish(). Context-scoped like the metrics
# registry so concurrent worker builds never observe each other's
# chunks. Observers must be thread-safe and non-raising: they are
# called from pool workers on the commit hot path.
_chunk_observer: "contextvars.ContextVar" = contextvars.ContextVar(
    "makisu_chunk_observer", default=None)


def set_chunk_observer(cb):
    """Bind a per-context fingerprint callback ``cb(hex_digest)``.
    Returns a token for :func:`reset_chunk_observer`."""
    return _chunk_observer.set(cb)


def reset_chunk_observer(token) -> None:
    _chunk_observer.reset(token)


# Lane-buffer buckets: (capacity, lanes). Chunk avg is 8 KiB and max
# 64 KiB, so most chunks hash in the 16 KiB bucket; each bucket is one
# compiled XLA program reused forever.
_BUCKETS = ((16 * 1024, 512), (gear.DEFAULT_MAX_SIZE + 64, 128))


class Chunk(typing.NamedTuple):
    # NamedTuple, not a frozen dataclass: sessions create one per
    # ~8KiB chunk (~130k/GB), and tuple construction is ~5x cheaper
    # than frozen-dataclass __setattr__ — measurable on the native
    # serial route. Field access is unchanged.
    offset: int
    length: int
    digest: bytes  # 32-byte sha256

    @property
    def hex(self) -> str:
        return self.digest.hex()


class FeedClock:
    """Where one stream's device feed spends the host's time, and what
    it moves: monotonic seconds per stage (``gear_dispatch``,
    ``gear_readback``, ``host_cut``, ``sha_dispatch``, ``sha_readback``,
    ``service_wait``, and beside it ``service_submit``, the part of it
    blocked in ``HashService.submit``) and bytes per crossing, kept in
    plain numbers and flushed once (``ChunkSession.finish``, or per
    batch by the shared hash service) into
    ``makisu_commit_stage_busy_seconds{stage}`` and
    ``makisu_device_transfer_bytes_total{direction,stage}``. A stage
    entered inside another (a lane dispatch during the host cut) is
    charged to itself alone. Each stage is also a bare annotation on the
    profiler's host timeline. One thread at a time."""

    def __init__(self) -> None:
        self.seconds: dict[str, float] = collections.defaultdict(float)
        self.bytes: dict[tuple[str, str], int] = \
            collections.defaultdict(int)
        self._nested = 0.0  # seconds of stages closed at this depth

    @contextlib.contextmanager
    def stage(self, name: str):
        t0 = time.monotonic()
        nested0 = self._nested
        with metrics.annotation(name):
            try:
                yield
            finally:
                self.add(name, time.monotonic() - t0,
                         self._nested - nested0)

    def add(self, name: str, elapsed: float, nested: float = 0.0) -> None:
        self.seconds[name] += elapsed - nested
        self._nested += elapsed - nested

    def moved(self, direction: str, stage: str, nbytes: int) -> None:
        self.bytes[direction, stage] += nbytes

    def flush(self) -> None:
        for name, seconds in self.seconds.items():
            metrics.stage_busy_add(name, seconds)
        for (direction, stage), nbytes in self.bytes.items():
            metrics.counter_add(metrics.DEVICE_TRANSFER_BYTES, nbytes,
                                direction=direction, stage=stage)
        self.seconds.clear()
        self.bytes.clear()


class _LaneBatcher:
    """Accumulates chunks into one bucket's fixed [L, CAP] buffer and
    dispatches sha256_lanes when full."""

    def __init__(self, cap: int, lanes: int,
                 route: "_route.ChunkRoute | None",
                 clock: FeedClock, notify=None) -> None:
        self.cap = cap
        self.lanes = lanes
        self.route = route
        self.clock = clock
        self.notify = notify  # called with each digest's hex, if given
        self.data = np.zeros((lanes, cap), dtype=np.uint8)
        self.lengths = np.zeros(lanes, dtype=np.int32)
        self.meta: list[tuple[int, int]] = []  # (offset, length)
        self.pending: list[tuple[jax.Array, list[tuple[int, int]]]] = []

    def add(self, off: int, data: memoryview) -> None:
        i = len(self.meta)
        n = len(data)
        self.data[i, :n] = np.frombuffer(data, dtype=np.uint8)
        self.data[i, n:] = 0
        self.lengths[i] = n
        self.meta.append((off, n))
        if len(self.meta) == self.lanes:
            self.flush()

    def flush(self) -> None:
        if not self.meta:
            return
        with self.clock.stage("sha_dispatch"):
            digests = _route.hash_lanes(
                self.route, self.data, self.lengths)  # async dispatch
        self.clock.moved("h2d", "sha",
                         self.data.nbytes + self.lengths.nbytes)
        metrics.counter_add("makisu_bytes_hashed_total",
                            sum(n for _, n in self.meta),
                            backend=self.route.sha, path="cdc")
        self.pending.append((digests, self.meta))
        self.meta = []
        # Fresh buffers: the dispatched call may still be consuming the old
        # host arrays.
        self.data = np.zeros((self.lanes, self.cap), dtype=np.uint8)
        self.lengths = np.zeros(self.lanes, dtype=np.int32)

    def drain(self) -> list[Chunk]:
        self.flush()
        out: list[Chunk] = []
        for digests, meta in self.pending:
            t0 = time.monotonic()
            with self.clock.stage("sha_readback"):
                host = _backend.sync_bounded(
                    digests, "lane digest readback")  # bounded sync point
            self.clock.moved("d2h", "sha", host.nbytes)
            # Readback-wait per program (timed around the sync only:
            # dispatch was async at flush and the host kept scanning in
            # between — flush-to-drain wall time would charge that host
            # work to the device and poison the per-bucket digests the
            # shared HashService exports under the same names).
            _backend.note_device_dispatch(
                self.cap, self.lanes, len(meta),
                sum(n for _, n in meta), time.monotonic() - t0)
            for i, (off, n) in enumerate(meta):
                digest = host[i].astype(">u4").tobytes()
                out.append(Chunk(off, n, digest))
                if self.notify is not None:
                    self.notify(digest.hex())
        self.pending = []
        return out


class ChunkSession:
    """One layer stream → content-defined chunks with fingerprints."""

    # How many gear dispatches may be in flight before the host blocks on
    # the oldest bitmap. Depth 2 overlaps device scan + readback with the
    # caller producing the next block (tar writing / file IO).
    PIPELINE_DEPTH = 2

    def __init__(self, avg_bits: int = gear.DEFAULT_AVG_BITS,
                 min_size: int = gear.DEFAULT_MIN_SIZE,
                 max_size: int = gear.DEFAULT_MAX_SIZE,
                 block: int = BLOCK, service=None,
                 workers: int | None = None) -> None:
        if block % 32:
            raise ValueError("block size must be a multiple of 32")
        # Optional chunker.service.HashService: concurrent builds in one
        # process share full device batches instead of dispatching their
        # own (worker mode / build farms).
        self.service = service
        self._service_pending: list[tuple[int, int, object]] = []
        self.avg_bits = avg_bits
        self.min_size = min_size
        self.max_size = max_size
        self.block = block
        self._staging = bytearray()   # bytes not yet gear-scanned
        self._tail = bytearray()      # scanned bytes after the last cut
        self._tail_offset = 0         # stream offset of _tail[0]
        self._scanned = 0             # stream bytes gear-dispatched so far
        self._halo = b""              # last WINDOW bytes of previous block
        self._prev_cut = 0            # stream offset of the last cut
        self._inflight: list[tuple] = []  # dispatched, unprocessed blocks
        self._batchers: list[_LaneBatcher] = []
        self._chunks: list[Chunk] = []
        # Batched-route state, defaulted before the backend probe below
        # (whose _degrade clears them). Pending chunks are (offset,
        # length) records tiling the tail's prefix [_tail_offset,
        # _prev_cut); the flush consumes that prefix in one slice and
        # one GIL-released native call.
        self._sha_meta: list[tuple[int, int]] = []  # (offset, length)
        self._sha_pending: list = []  # ordered (meta, Future->digests)
        self._degraded: str | None = None  # failure summary once degraded
        self._clock = FeedClock()  # device-feed seconds and bytes
        # The route (chunker/route.py), decided once per process from
        # what the backend probe found. The probe is the hang guard: a
        # backend init that blocks (chip held by another process) never
        # raises, so the wait for it is bounded and cached
        # process-wide, and a failed probe is a device-plane failure
        # like any mid-stream one. CPU hosts (build boxes with no
        # accelerator) take the native route: the runtime-dispatched
        # C++ gear scan (AVX2 / striped / scalar) + batch SHA-256
        # (SHA-NI / EVP / scalar), bit-identical to the device
        # formulation and ~10x driving XLA's CPU backend through the
        # vector form. The service path (cross-build device batching)
        # and non-cpu backends keep the device route.
        self._route = None
        self._observer = _chunk_observer.get()
        try:
            self._route = _route.chunk_route(shared=service is not None)
        except RuntimeError as e:
            self._degrade("backend init", e)
        self._native = self._route is not None and self._route.native
        if (self._route is not None and not self._native
                and service is None):
            # Each batch's digests stream to the observer as its
            # readback lands, while the batches behind it are read back.
            self._batchers = [
                _LaneBatcher(cap, lanes, self._route, self._clock,
                             self._observer and self._notify)
                for cap, lanes in _BUCKETS]
        # The gear table is deterministic by contract; one copy per
        # session, not one 256-iteration rebuild per 4MiB block.
        self._table = gear.gear_table() if self._native else None
        # Bytes hashed on the native route, accumulated locally and
        # flushed once at finish(): a per-chunk counter_add (lock +
        # label sort, ×2 registries) measured ~13% of the whole native
        # session.
        self._native_hashed = 0
        # Multicore native route (the tentpole): gear block scans and
        # chunk SHA-256 run on the shared commit pool, with results
        # consumed in stream order so boundaries, digests, and chunk
        # ordering are byte-identical to the serial route. workers=1
        # is exactly the serial pipeline.
        self._workers = 1
        self._depth = self.PIPELINE_DEPTH
        self._pool = None
        self._sha_slots = None
        if self._native:
            self._workers = (concurrency.hash_workers()
                             if workers is None else max(1, workers))
            if self._workers > 1:
                import threading
                self._pool = concurrency.hash_pool()
                # Scan deep enough that every worker can hold a block.
                self._depth = max(self.PIPELINE_DEPTH, self._workers)
                # Backpressure AND concurrency bound: at most `workers`
                # SHA batches in flight, so one session never runs more
                # simultaneous tasks than its configured parallelism on
                # the shared pool (oversubscription measured as a 3x
                # LOSS: 8 tasks + the producer thrashing 2 cores), and
                # resident batch bytes stay ≤ workers × SHA_BATCH_BYTES.
                self._sha_slots = threading.BoundedSemaphore(
                    self._workers)
                self._sha_depth = 0
                self._sha_depth_lock = threading.Lock()

    # -- failure discipline ----------------------------------------------

    def _degrade(self, stage: str, exc: Exception) -> None:
        """Device-plane failure: raise it, failing the build with its
        reason. Only under ``MAKISU_TPU_CHUNK_STRICT=0`` drop chunk
        tracking for this layer and let the build continue (whole-layer
        caching only). Never corrupts — a degraded layer simply has no
        fingerprints."""
        import os

        from makisu_tpu.utils import logging as log
        if os.environ.get("MAKISU_TPU_CHUNK_STRICT") != "0":
            raise exc
        log.warning(
            "chunk fingerprinting disabled for this layer (%s: %s); "
            "build continues with whole-layer caching only", stage, exc)
        # Summary string, NOT the exception: its traceback would pin
        # the failing frames (4MiB blocks, numpy buffers) that the
        # clears below exist to release.
        self._degraded = f"{stage}: {exc}"
        self._staging.clear()
        self._tail.clear()
        self._inflight = []
        self._chunks = []
        self._service_pending = []
        # Batched-route state: pending tasks complete harmlessly on the
        # shared pool (they release their own slots); just drop the
        # references so their buffers free.
        self._sha_meta = []
        self._sha_pending = []
        for b in self._batchers:
            b.meta = []
            b.pending = []

    # -- byte intake ------------------------------------------------------

    def update(self, data: bytes) -> None:
        if self._degraded is not None:
            return
        self._staging.extend(data)
        while len(self._staging) >= self.block:
            # The scan buffer is assembled ONCE with the halo prefix in
            # place (join accepts the staging memoryview directly): one
            # copy instead of three (bytearray slice → bytes() → the
            # old per-scan halo+blk concat) — a full stream pass saved
            # on every route.
            halo_len = len(self._halo)
            with memoryview(self._staging) as mv:
                hblk = b"".join((self._halo, mv[:self.block]))
            del self._staging[:self.block]
            try:
                # (the dispatch also drains the oldest in-flight block
                # when the pipeline is full, so readback errors can
                # surface here too — hence the broader stage label)
                self._dispatch_block(hblk, halo_len, self.block)
            except Exception as e:  # noqa: BLE001 - device plane
                self._degrade("gear pipeline", e)
                return

    def finish(self) -> list[Chunk]:
        if self._degraded is None and self._staging:
            live = len(self._staging)
            pad = (-live) % 32  # exactly the pre-halo-prefix padding
            halo_len = len(self._halo)
            with memoryview(self._staging) as mv:
                hblk = b"".join((self._halo, mv, b"\x00" * pad))
            try:
                self._dispatch_block(hblk, halo_len, live)
            except Exception as e:  # noqa: BLE001 - device plane
                self._degrade("gear pipeline", e)
            self._staging.clear()
        while self._degraded is None and self._inflight:
            try:
                self._process_block(self._inflight.pop(0))
            except Exception as e:  # noqa: BLE001 - device plane
                self._degrade("gear readback", e)
        # Final chunk: whatever follows the last cut. _take routes it
        # like any forced cut — straight to the batch record on the
        # batched routes (the tail may still hold pending batch bytes,
        # so it must NOT be cleared here), immediate emit elsewhere.
        if self._degraded is None:
            stream_end = self._tail_offset + len(self._tail)
            if stream_end > self._prev_cut:
                try:
                    self._take(stream_end)
                except Exception as e:  # noqa: BLE001 - device plane
                    self._degrade("lane dispatch", e)
        if self._degraded is None:
            try:
                if self._native:
                    self._flush_sha_batch()
                if self._pool is not None:
                    for meta, fut in self._sha_pending:
                        raw = fut.result().tobytes()
                        self._chunks.extend(
                            Chunk(off, n, raw[32 * i:32 * i + 32])
                            for i, (off, n) in enumerate(meta))
                    self._sha_pending = []
                for b in self._batchers:
                    self._chunks.extend(b.drain())
                if self._service_pending:
                    _t = _backend.sync_timeout()
                    svc_timeout = _t if _t > 0 else None
                    with self._clock.stage("service_wait"):
                        for offset, length, fut in self._service_pending:
                            # Bounded like the direct readbacks: a dead
                            # service dispatcher must degrade the
                            # layer, not block it.
                            self._chunks.append(
                                Chunk(offset, length,
                                      fut.result(timeout=svc_timeout)))
            except Exception as e:  # noqa: BLE001 - device plane
                self._degrade("lane hashing", e)
        # Once per stream, degraded or not: what the feed did happen.
        self._clock.flush()
        if self._native_hashed:
            # One flush for the whole stream (a per-chunk counter_add
            # measured ~13% of the native session); degraded sessions
            # still record the bytes they DID hash.
            metrics.counter_add("makisu_bytes_hashed_total",
                                self._native_hashed,
                                backend="native", path="cdc")
            self._native_hashed = 0
        if self._pool is not None:
            # The session is drained: a long-lived worker's /metrics
            # must not keep showing the last submit-time backlog.
            metrics.stage_queue_depth("gear_scan", 0)
            metrics.stage_queue_depth("chunk_sha", 0)
        if self._degraded is not None:
            return []
        self._service_pending = []
        self._chunks.sort(key=lambda c: c.offset)
        if self._chunks:
            # One batched fold per stream (never per chunk): chunking
            # efficiency — are cuts landing near the 8KiB target, or
            # degenerating to min/max forced cuts? — visible in
            # /metrics without a ledger.
            metrics.observe_batch("makisu_chunk_size_bytes",
                                  [c.length for c in self._chunks],
                                  buckets=CHUNK_SIZE_BUCKETS)
        return self._chunks

    # -- internals --------------------------------------------------------

    def _dispatch_block(self, hblk: bytes, halo_len: int,
                        live: int) -> None:
        """Ship one block to the scan stage (device dispatch, or the
        commit pool on the multicore native route); process the oldest
        in-flight block when the pipeline is full.

        ``hblk`` arrives with the previous block's halo already in
        place (``hblk[:halo_len]``) and the live stream bytes at
        ``hblk[halo_len:halo_len + live]`` (anything after is zero
        padding on the final block) — assembled once by the caller, so
        no scan route re-concatenates the 4MiB buffer."""
        from makisu_tpu.ops import gear_pallas
        route = self._route
        if self._native:
            if self._pool is not None:
                # Pooled scan: each block's candidates are a pure
                # function of (halo, block) — the same inputs the
                # synchronous scan sees — so blocks scan in parallel
                # across the pool while _process_block consumes results
                # in stream order. Boundaries are byte-identical.
                fut = concurrency.submit_ctx(
                    self._pool, self._scan_task, hblk, halo_len, live)
                entry = ("native", fut, halo_len, live, hblk,
                         self._scanned)
                metrics.stage_queue_depth("gear_scan",
                                          len(self._inflight) + 1)
            else:
                # Synchronous by design: the scan is faster than a
                # device round trip, so there is nothing to overlap.
                # The C++ scan returns candidate POSITIONS directly —
                # no bit array, no host-side nonzero rescan.
                entry = ("native",
                         self._scan_positions(hblk, halo_len, live),
                         halo_len, live, hblk, self._scanned)
        elif route.gear == "pallas":
            # Fused kernel (the default on TPU). Restaging runs on
            # device inside the same program. The live region is
            # zero-padded to the kernel's 64 KiB row-grid granularity
            # so distinct tail-block sizes share compiles.
            with self._clock.stage("gear_dispatch"):
                qbuf = gear_pallas.quantize_flat(
                    np.frombuffer(hblk, dtype=np.uint8), halo_len, live)
                words = gear_pallas.gear_bitmap_flat(
                    qbuf, halo_len, self.avg_bits,
                    interpret=route.interpret)
            self._clock.moved("h2d", "gear", qbuf.nbytes)
            entry = ("pallas", words, gear_pallas.nrows_for(live),
                     live, hblk, self._scanned, halo_len)
        else:
            with self._clock.stage("gear_dispatch"):
                words = gear.gear_bitmap(
                    np.frombuffer(hblk, dtype=np.uint8),
                    self.avg_bits)  # async dispatch
            self._clock.moved("h2d", "gear", len(hblk))
            entry = ("xla", words, halo_len, live, hblk, self._scanned)
        metrics.counter_add("makisu_gear_scan_bytes_total", live,
                            backend=route.gear)
        self._inflight.append(entry)
        self._scanned += live
        # Next block's halo: the last HALO live bytes (padding excluded;
        # byte-identical to the old (halo+blk)[-HALO:]).
        end = halo_len + live
        self._halo = hblk[max(0, end - gear_pallas.HALO):end]
        while len(self._inflight) > self._depth:
            self._process_block(self._inflight.pop(0))

    def _scan_positions(self, hblk: bytes, halo_len: int, live: int):
        """Candidate positions for one block (native C++ scan): the
        shared math of the synchronous and pooled routes — positions
        over the halo-prefixed buffer, trimmed to the live region,
        halo-relative."""
        from makisu_tpu import native
        buf = np.frombuffer(hblk, dtype=np.uint8)
        pos = native.gear_scan_positions(
            buf, self._table, (1 << self.avg_bits) - 1)
        lo = np.searchsorted(pos, halo_len)
        hi = np.searchsorted(pos, halo_len + live)
        return pos[lo:hi] - halo_len

    def _scan_task(self, hblk: bytes, halo_len: int, live: int):
        t0 = time.monotonic()
        try:
            return self._scan_positions(hblk, halo_len, live)
        finally:
            metrics.stage_busy_add("gear_scan", time.monotonic() - t0)

    def _process_block(self, entry: tuple) -> None:
        """Read back one block's bitmap (bounded sync) and cut chunks."""
        kind, words, meta, live, hblk, base = entry[:6]
        if kind == "native":
            halo_len = meta
            if hasattr(words, "result"):
                # Pooled scan: block until THIS block's candidates are
                # in (stream order preserved; a task error propagates
                # here and degrades the session like any scan failure).
                words = words.result()
            candidates = words.astype(np.int64) + base  # host positions
            self._cut_block(candidates, hblk, halo_len, live)
            return
        with self._clock.stage("gear_readback"):
            host_words = _backend.sync_bounded(
                words, "gear bitmap readback")
        self._clock.moved("d2h", "gear", host_words.nbytes)
        # Bit unpack, the cut policy and lane packing; the lane
        # dispatches it triggers are charged to themselves.
        with self._clock.stage("host_cut"):
            if kind == "pallas":
                from makisu_tpu.ops import gear_pallas
                nrows = meta
                halo_len = entry[6]
                bits = gear.unpack_bits_np(
                    host_words[:nrows], nrows * gear_pallas.ROW)
                candidates = np.nonzero(
                    bits.reshape(-1)[:live])[0] + base
            else:
                halo_len = meta
                bits = gear.unpack_bits_np(
                    host_words, halo_len + live)[halo_len:halo_len + live]
                candidates = np.nonzero(bits)[0] + base
            self._cut_block(candidates, hblk, halo_len, live)

    def _cut_block(self, candidates, hblk: bytes, halo_len: int,
                   live: int) -> None:
        """Apply the cut policy to one block's candidate positions."""
        with memoryview(hblk) as mv:
            self._tail.extend(mv[halo_len:halo_len + live])
        # tolist(): one C conversion instead of a numpy-scalar __int__
        # per candidate on the producer's critical path.
        for pos in candidates.tolist():
            self._cut_to(pos + 1)  # cut AFTER the boundary byte
        # Oversize uncut span without candidates: force max-size cuts.
        # (Measured from the last cut, not the tail start — on the
        # batched routes the tail also holds pending batch bytes.)
        while (self._tail_offset + len(self._tail) - self._prev_cut
               > self.max_size):
            self._force_cut(self._prev_cut + self.max_size)

    def _cut_to(self, end: int) -> None:
        if end - self._prev_cut < self.min_size:
            return
        while end - self._prev_cut > self.max_size:
            self._force_cut(self._prev_cut + self.max_size)
        if end - self._prev_cut >= self.min_size:
            self._take(end)

    def _force_cut(self, end: int) -> None:
        self._take(end)

    def _take(self, end: int) -> None:
        n = end - self._prev_cut
        if n <= 0:
            return
        if self._native:
            # Batched path (the native route, pooled and serial: one
            # GIL-released call per ~MiB batch, SHA-NI multi-buffer
            # when the CPU has it, instead of ~128 per-chunk hashlib
            # round trips — same digests, same order): no per-chunk
            # byte shuffling at all. Chunks tile the stream, so the
            # pending batch IS the tail's prefix [_tail_offset,
            # _prev_cut) — _take just records (offset, length) and the
            # flush consumes that prefix in ONE slice + ONE native call
            # (the old per-chunk memoryview copies were ~2s/GB of pure
            # Python on the serial route).
            self._sha_meta.append((self._prev_cut, n))
            self._native_hashed += n
            self._prev_cut = end
            if end - self._tail_offset >= SHA_BATCH_BYTES:
                self._flush_sha_batch()
            return
        # Immediate path (device lanes / service): nothing defers
        # here, so the tail starts at the chunk start (_prev_cut ==
        # _tail_offset) and is consumed chunk by chunk. The memoryview
        # must close before the del: a bytearray with an exported
        # buffer cannot resize.
        with memoryview(self._tail) as mv:
            data = bytes(mv[:n])
        del self._tail[:n]
        self._emit(data, self._tail_offset)
        self._tail_offset = end
        self._prev_cut = end

    def _notify(self, hex_digest: str) -> None:
        """Stream one fingerprint to the bound observer (chunk-dedup
        cache prefetch). Never raises: a cache-side hiccup must not
        degrade fingerprinting."""
        if self._observer is None:
            return
        try:
            self._observer(hex_digest)
        except Exception:  # noqa: BLE001 - observer plane
            self._observer = None  # one failure disables, not N

    def _notify_resolved(self, fut) -> None:
        """Done-callback of a shared-service future: a digest goes to
        the observer, a failure is finish()'s to raise."""
        if not fut.cancelled() and fut.exception() is None:
            self._notify(fut.result().hex())

    def _flush_sha_batch(self) -> None:
        if not self._sha_meta:
            return
        meta = self._sha_meta
        self._sha_meta = []
        # The batch is the tail prefix the recorded chunks tile:
        # [_tail_offset, _prev_cut) in stream coordinates.
        consumed = self._prev_cut - self._tail_offset
        lengths = [n for _, n in meta]
        if self._pool is None:
            # Serial native route: hash the batch NOW — ONE
            # GIL-released native call (runtime-dispatched: SHA-NI
            # multi-buffer / EVP / scalar) straight out of the tail
            # buffer, zero-copy (nothing mutates the tail during a
            # synchronous call). Digests are byte-identical to hashlib.
            from makisu_tpu import native
            with memoryview(self._tail) as mv:
                digests = native.sha256_batch(mv[:consumed], lengths)
            del self._tail[:consumed]
            self._tail_offset = self._prev_cut
            raw = digests.tobytes()  # ONE copy; bytes slicing is cheap
            if self._observer is None:
                self._chunks.extend(
                    Chunk(off, n, raw[32 * i:32 * i + 32])
                    for i, (off, n) in enumerate(meta))
            else:
                for i, (off, n) in enumerate(meta):
                    digest = raw[32 * i:32 * i + 32]
                    self._chunks.append(Chunk(off, n, digest))
                    self._notify(digest.hex())
            return
        # Pooled route: copy the prefix ONCE into the task's own buffer
        # (the producer keeps mutating the tail while the task runs).
        with memoryview(self._tail) as mv:
            buf = bytes(mv[:consumed])
        del self._tail[:consumed]
        self._tail_offset = self._prev_cut
        self._sha_slots.acquire()  # released by the task (backpressure)
        with self._sha_depth_lock:
            self._sha_depth += 1
            depth = self._sha_depth
        metrics.stage_queue_depth("chunk_sha", depth)
        self._sha_pending.append(
            (meta, concurrency.submit_ctx(self._pool, self._sha_task,
                                          buf, lengths)))

    def _sha_task(self, buf: bytes, lengths: list[int]):
        """Pool-side chunk hashing: ONE GIL-released native call for
        the whole batch (digests byte-identical to hashlib — same
        OpenSSL underneath). Deliberately does nothing else: every
        extra GIL acquisition on a pool thread can stall a full switch
        interval behind the GIL-bound producer, so batch assembly
        happens in _take/_flush_sha_batch and Chunk objects are built
        at finish()."""
        from makisu_tpu import native
        t0 = time.monotonic()
        try:
            digests = native.sha256_batch(buf, lengths)
            if self._observer is not None:
                for row in digests:
                    self._notify(row.tobytes().hex())
            return digests
        finally:
            with self._sha_depth_lock:
                self._sha_depth -= 1
            self._sha_slots.release()
            metrics.stage_busy_add("chunk_sha", time.monotonic() - t0)

    def _emit(self, data: bytes, offset: int) -> None:
        if self.service is not None:
            # A full service queue blocks the build here (backpressure).
            t0 = time.monotonic()
            fut = self.service.submit(data, owner=id(self))
            if self._observer is not None:
                # On the service's thread, as the batch's readback
                # lands: the digest streams out while this build is
                # still writing its tar, not when finish() collects it.
                fut.add_done_callback(self._notify_resolved)
            blocked = time.monotonic() - t0
            self._clock.add("service_wait", blocked)
            # Beside it, not nested in it: the backpressure alone.
            self._clock.seconds[metrics.SERVICE_SUBMIT_STAGE] += blocked
            self._service_pending.append((offset, len(data), fut))
            return
        for b in self._batchers:
            if len(data) <= b.cap - 64:  # leave room for sha padding
                b.add(offset, memoryview(data))
                return
        raise AssertionError(
            f"chunk of {len(data)} bytes exceeds every lane bucket")
