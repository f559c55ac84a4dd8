"""Hasher implementations behind the layer-commit seam.

``LayerSink`` is the writable object a layer tar streams into; ``finish()``
yields the layer's identity: tar digest (diffID), gzip blob descriptor, and
(TPU path) content-defined chunk fingerprints.

Reference hot path replaced: lib/builder/step/common.go tarAndGzipDiffs:35
(tar bytes → two sequential SHA-256 digesters + pgzip via nested
ConcurrentMultiWriters).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import BinaryIO, Protocol

from makisu_tpu import tario
from makisu_tpu.docker.image import (
    MEDIA_TYPE_LAYER,
    Descriptor,
    Digest,
    DigestPair,
)
from makisu_tpu.utils import events, metrics
from makisu_tpu.utils import logging as log

# Under ``sink_finish``: the gzip stream's end (its last block, the pool
# or ring drained, the trailer) and, with a chunk session, the device's
# (the last dispatches, the readbacks, the chunk-SHA tail).
STREAM_JOIN_SPAN = "sink_finish.stream_join"
DEVICE_DRAIN_SPAN = "sink_finish.device_drain"


@functools.lru_cache(maxsize=None)
def _announce(sink: str, backend_id: str, lanes: int) -> None:
    """Log what commits this process's layers the first time it does:
    once per process and decision (as ``chunk route:``). ``lanes`` is
    the context's ``compress_workers()``, which only the block format
    can use."""
    backend, _, rest = backend_id.partition("-")
    level, _, block = rest.partition("-")
    log.info("layer sink: %s backend=%s level=%s lanes=%d block=%s", sink,
             backend, level, lanes if backend == "pgzip" else 1,
             block or "none (one stream)")


@dataclasses.dataclass(frozen=True)
class ChunkFingerprint:
    offset: int
    length: int
    hex_digest: str


@dataclasses.dataclass
class LayerCommit:
    """Everything the cache/registry need to know about one layer."""

    digest_pair: DigestPair
    chunks: list[ChunkFingerprint]
    # Compression identity the blob was written with (cache entries
    # record it so chunk reconstitution replays byte-identically).
    gzip_backend_id: str = ""

    @property
    def chunk_ids(self) -> list[str]:
        return [c.hex_digest for c in self.chunks]


class LayerSink:
    """CPU layer sink: gzip + (tar digest, gzip digest) streaming.

    Subclasses tap the uncompressed tar stream for extra work.

    On multicore hosts, compression runs on a worker thread behind a
    bounded queue so the tar digest (and TPU tap) overlap with gzip —
    the reference's ConcurrentMultiWriter fan-out
    (lib/stream/multi_writer.go:25, lib/builder/step/common.go:47-56).
    Both hashlib and zlib release the GIL, so the overlap is real.
    With the pgzip backend the writer behind the queue is itself the
    block-parallel compress stage (tario.BlockGzipWriter): deflate
    fans out across the shared hash pool at ``compress_workers()``
    lanes, byte-identical at every count.
    """

    def __init__(self, out: BinaryIO, backend_id: str | None = None,
                 threaded: bool | None = None) -> None:
        import os as _os
        self._tar_digest = hashlib.sha256()
        self._nbytes = 0  # uncompressed bytes digested (telemetry)
        self._writes = 0  # queue-depth sampling stride
        self._tee = tario.TeeDigest(out)
        self.backend_id = backend_id or tario.gzip_backend_id()
        self._gz = tario.gzip_writer(self._tee, backend_id=self.backend_id)
        self._closed = False
        from makisu_tpu.utils import concurrency
        _announce("python", self.backend_id, concurrency.compress_workers())
        if threaded is None:
            threaded = (_os.cpu_count() or 1) > 1
        self._queue = None
        self._worker = None
        self._worker_error: list[BaseException] = []
        if threaded:
            import contextvars
            import queue
            import threading
            import time as _time
            self._queue = queue.Queue(maxsize=8)

            # A block-parallel writer (tario.BlockGzipWriter) reports
            # its own compress busy seconds from its pool lanes; this
            # feed thread's write() is then just buffering + batch
            # submission, and charging it too would double-count the
            # stage.
            self_reporting = getattr(self._gz, "reports_compress_busy",
                                     False)

            def run() -> None:
                # Busy time accumulates locally and flushes once at
                # stream end — per-write counter churn would become
                # the overhead it measures.
                busy = 0.0
                try:
                    while True:
                        item = self._queue.get()
                        if item is None:
                            return
                        t0 = _time.monotonic()
                        try:
                            self._gz.write(item)
                        except BaseException as e:  # noqa: BLE001
                            self._worker_error.append(e)
                            return
                        busy += _time.monotonic() - t0
                finally:
                    if not self_reporting:
                        metrics.stage_busy_add(metrics.COMPRESS_STAGE,
                                               busy)

            # copy_context: the stage counter must land in the build's
            # registry, not just the process-global one (threads start
            # with an empty context).
            self._worker = threading.Thread(
                target=contextvars.copy_context().run, args=(run,),
                daemon=True)
            self._worker.start()

    def _put_checked(self, item) -> None:
        """Bounded put that re-checks for a dead worker: if the
        compressor thread died while the queue was full, a plain put()
        would block forever and hang the build instead of surfacing
        the error."""
        import queue as queue_mod
        while True:
            try:
                self._queue.put(item, timeout=1.0)
                return
            except queue_mod.Full:
                if self._worker_error:
                    raise RuntimeError("layer compression failed") \
                        from self._worker_error[0]

    def write(self, data: bytes) -> int:
        if self._worker_error:
            raise RuntimeError("layer compression failed") \
                from self._worker_error[0]
        if self._queue is not None:
            # The queue hands data to the compressor thread AFTER this
            # call returns, so a mutable buffer (bytearray, memoryview
            # a tar writer recycles) must be copied — but immutable
            # bytes, the overwhelmingly common case, can be enqueued
            # as-is: a per-write copy on the layer hot path.
            self._put_checked(data if isinstance(data, bytes)
                              else bytes(data))
            self._writes += 1
            if not self._writes & 0xFF:  # sampled: writes are ~16KiB
                metrics.stage_queue_depth("compress",
                                          self._queue.qsize())
        self._tar_digest.update(data)
        self._nbytes += len(data)
        if self._queue is None:
            self._gz.write(data)
        self._tap(data)
        # Hashing a huge layer is minutes of pure CPU with no events
        # or logs; each landed buffer stamps the progress clock so the
        # stall watchdog never mistakes a hard-working commit for a
        # wedge (same discipline as httputil's stream loop).
        events.note_progress()
        return len(data)

    def _tap(self, data: bytes) -> None:  # pragma: no cover - hook
        pass

    def _finish_chunks(self) -> list[ChunkFingerprint]:
        return []

    def open_tar(self):
        """Tar writer whose stream feeds this sink (the commit path's
        single entry point for layer serialization)."""
        import tarfile
        return tarfile.open(fileobj=self, mode="w|")

    def finish(self) -> LayerCommit:
        if self._closed:
            raise RuntimeError("layer sink already finished")
        self._closed = True
        with metrics.span(STREAM_JOIN_SPAN):
            if self._queue is not None:
                self._put_checked(None)
                self._worker.join()
                if self._worker_error:
                    raise RuntimeError("layer compression failed") \
                        from self._worker_error[0]
            self._gz.close()
            self._tee.flush()
        pair = DigestPair(
            tar_digest=Digest.from_hex(self._tar_digest.hexdigest()),
            gzip_descriptor=Descriptor(
                MEDIA_TYPE_LAYER, self._tee.size,
                Digest.from_hex(self._tee.digest.hexdigest())))
        metrics.counter_add("makisu_bytes_hashed_total", self._nbytes,
                            backend="python", path="layer_sink")
        backend = self.backend_id.split("-", 1)[0]
        metrics.counter_add(metrics.COMPRESS_BYTES, self._nbytes,
                            backend=backend, direction="in")
        metrics.counter_add(metrics.COMPRESS_BYTES, self._tee.size,
                            backend=backend, direction="out")
        return LayerCommit(pair, self._finish_chunks(),
                           gzip_backend_id=self.backend_id)


class _NativeTarWriter:
    """tarfile.TarFile-shaped writer over the native pipeline: headers
    are rendered by Python's tarfile (byte-identical PAX output); file
    content, padding, hashing, and compression run in C++.
    ``add_entries`` takes a layer's entries a batch at a time, and the
    sink reads the batch's files ahead on threads of its own."""

    import tarfile as _tarfile
    _FMT = (_tarfile.PAX_FORMAT, _tarfile.ENCODING, "surrogateescape")

    def __init__(self, sink: "NativeLayerSink") -> None:
        self._sink = sink
        self._offset = 0
        self._closed = False

    @property
    def offset(self) -> int:
        """Bytes written so far, as ``tarfile.TarFile.offset``."""
        return self._offset

    def add_entries(self, items: list[tuple]) -> None:
        """A batch of ``(tarinfo, path | None)`` in tar order, in one
        call into C++: each header as ``tarfile.TarFile.addfile``
        renders it, and for a path (a regular file with content) the
        file's first ``tarinfo.size`` bytes and the padding to 512."""
        headers = [tarinfo.tobuf(*self._FMT) for tarinfo, _ in items]
        sizes = [0 if path is None else tarinfo.size
                 for tarinfo, path in items]
        self._sink._handle.write_entries(
            headers, [path for _, path in items], sizes)
        self._offset += sum(map(len, headers)) \
            + sum(size + -size % 512 for size in sizes)
        events.note_progress()  # hashing is progress (see LayerSink)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        # End of archive exactly as tarfile: two zero blocks, then pad
        # the stream to a RECORDSIZE multiple (cache-identity-bearing).
        import tarfile
        end = b"\0" * (2 * tarfile.BLOCKSIZE)
        rem = (self._offset + len(end)) % tarfile.RECORDSIZE
        if rem:
            end += b"\0" * (tarfile.RECORDSIZE - rem)
        self._offset += len(end)
        self._sink._handle.write(end)
        # The writer streams straight into the C++ handle, bypassing
        # sink.write — account its bytes for the sink's telemetry.
        self._sink._nbytes += self._offset

    def __enter__(self) -> "_NativeTarWriter":
        return self

    def __exit__(self, exc_type, *exc) -> None:
        # A commit that dies in here never reaches finish(): the sink's
        # compressor thread stops before ``out`` closes under it.
        done = False
        try:
            if exc_type is None:
                self.close()
                done = True
        finally:
            if not done:
                self._sink.abort()


class NativeLayerSink:
    """Layer sink backed by native/layersink.cpp: the whole per-byte
    pipeline (tar framing, dual sha256, gzip) runs in C++. With a
    ``session`` (TPU hasher) the uncompressed stream additionally taps
    into the chunker via a native callback, so CDC fingerprinting rides
    the same single pass."""

    def __init__(self, out: BinaryIO, backend_id: str | None = None,
                 session=None) -> None:
        from makisu_tpu import native
        from makisu_tpu.utils import concurrency
        self.backend_id = backend_id or tario.gzip_backend_id()
        self._nbytes = 0  # uncompressed bytes digested (telemetry)
        parts = self.backend_id.split("-")
        backend, level = parts[0], int(parts[1])
        block = int(parts[2]) if backend == "pgzip" else 0
        out.flush()  # nothing buffered may trail the native fd writes
        # The compress-workers knob governs the C++ block pool too —
        # same worker-count-is-throughput-only contract as the Python
        # stage (block bytes are a pure function of level/block size).
        lanes = concurrency.compress_workers()
        self._handle = native.LayerSinkHandle(
            out.fileno(), backend, level, block or native.DEFAULT_BLOCK,
            nthreads=lanes)
        _announce("native", self.backend_id, lanes)
        self._session = session
        if session is not None:
            self._handle.set_tap(session.update)

    def open_tar(self) -> _NativeTarWriter:
        return _NativeTarWriter(self)

    def write(self, data: bytes) -> int:  # parity with LayerSink
        self._handle.write(bytes(data))
        self._nbytes += len(data)
        events.note_progress()  # hashing is progress (see LayerSink)
        return len(data)

    def abort(self) -> None:
        """A commit that died between two entries: stop and join the
        library's compressor and reader threads now, while ``out`` is
        still open (``__del__`` would, but only once the traceback
        lets go)."""
        self._handle.close()

    def finish(self) -> LayerCommit:
        try:
            with metrics.span(STREAM_JOIN_SPAN):
                tar_hex, gz_hex, gz_size, _ = self._handle.finish()
            # The stage the Python sink's compressor thread reports:
            # here deflate runs on the library's own thread (zlib) or
            # block pool (pgzip), and the library keeps the seconds.
            # compress is the seconds the stream kept threads busy,
            # summed over the pool's lanes; compress_wall the seconds
            # it had a block queued or deflating (one thread: the
            # same); compress_wait what this thread spent blocked on
            # it (a full ring or pool, the drain just now): near 0
            # where the producer is the brake; blob_write what this
            # thread spent digesting and writing the pool's blocks
            # (0 under zlib, whose compressor thread does that itself:
            # no such stage there).
            busy = self._handle.compress_seconds()
            wall = self._handle.wall_seconds()
            waited = self._handle.wait_seconds()
            blob_write = self._handle.blob_write_seconds()
            prefetch = self._handle.prefetch_stats()
        finally:
            self._handle.close()
        metrics.stage_busy_add(metrics.COMPRESS_STAGE, busy)
        metrics.stage_busy_add("compress_wall", wall)
        metrics.stage_busy_add("compress_wait", waited)
        if blob_write:
            metrics.stage_busy_add("blob_write", blob_write)
        # A part of tar_write: what this thread spent blocked on one of
        # the sink's readers, and how each file's bytes came.
        metrics.stage_busy_add("read_wait", prefetch[0])
        for result, n in zip(("ready", "waited", "streamed"),
                             prefetch[1:]):
            if n:
                metrics.counter_add(metrics.SINK_PREFETCH_FILES_TOTAL,
                                    n, result=result)
        metrics.counter_add("makisu_bytes_hashed_total", self._nbytes,
                            backend="native", path="layer_sink")
        backend = self.backend_id.split("-", 1)[0]
        metrics.counter_add(metrics.COMPRESS_BYTES, self._nbytes,
                            backend=backend, direction="in")
        metrics.counter_add(metrics.COMPRESS_BYTES, gz_size,
                            backend=backend, direction="out")
        pair = DigestPair(
            tar_digest=Digest.from_hex(tar_hex),
            gzip_descriptor=Descriptor(MEDIA_TYPE_LAYER, gz_size,
                                       Digest.from_hex(gz_hex)))
        chunks = []
        if self._session is not None:
            with metrics.span(DEVICE_DRAIN_SPAN):
                chunks = [ChunkFingerprint(c.offset, c.length, c.hex)
                          for c in self._session.finish()]
        return LayerCommit(pair, chunks, gzip_backend_id=self.backend_id)


class Hasher(Protocol):
    """Factory for layer sinks; chosen once per build."""

    name: str

    def open_layer(self, out: BinaryIO,
                   backend_id: str | None = None) -> LayerSink: ...


def _native_sink_enabled() -> bool:
    import os
    if os.environ.get("MAKISU_TPU_NATIVE_SINK") == "0":
        return False
    from makisu_tpu import native
    return native.layersink_available()


def _use_native(out: BinaryIO, backend_id: str | None = None) -> bool:
    """One decision point for native-vs-Python pipelines (the choice is
    cache-identity-neutral but must be consistent across hashers):
    native needs a real fd; in-memory outputs (tests) take Python.

    zlib level 0 is excluded: stored-block framing depends on write
    granularity, and the C++ pipeline feeds deflate at a different
    granularity than the (pinned, see tario._FixedGranularityWriter)
    Python path — choosing native there would split cache identity by
    host capability."""
    if not _native_sink_enabled():
        return False
    if (backend_id or tario.gzip_backend_id()) == "zlib-0":
        return False
    try:
        out.fileno()
    except (OSError, AttributeError, ValueError):
        return False
    return True


class CPUHasher:
    """Parity with the reference: digests only, no chunking. Uses the
    native C++ pipeline when available (MAKISU_TPU_NATIVE_SINK=0 forces
    the pure-Python path)."""

    name = "cpu"

    def open_layer(self, out: BinaryIO,
                   backend_id: str | None = None) -> LayerSink:
        if _use_native(out, backend_id):
            return NativeLayerSink(out, backend_id=backend_id)
        return LayerSink(out, backend_id=backend_id)


class _TPUSink(LayerSink):
    def __init__(self, out: BinaryIO, session,
                 backend_id: str | None = None) -> None:
        super().__init__(out, backend_id=backend_id)
        self._session = session

    def _tap(self, data: bytes) -> None:
        self._session.update(data)

    def _finish_chunks(self) -> list[ChunkFingerprint]:
        with metrics.span(DEVICE_DRAIN_SPAN):
            return [ChunkFingerprint(c.offset, c.length, c.hex)
                    for c in self._session.finish()]


class TPUHasher:
    """CPU digests + accelerator-side CDC chunk fingerprints.

    ``shared=True`` routes chunk hashing through the process-wide
    HashService so concurrent builds fill common device batches
    (worker/build-farm mode).
    """

    name = "tpu"

    def __init__(self, avg_bits: int | None = None,
                 min_size: int | None = None,
                 max_size: int | None = None,
                 shared: bool = False) -> None:
        from makisu_tpu.ops import gear
        self.avg_bits = avg_bits or gear.DEFAULT_AVG_BITS
        self.min_size = min_size or gear.DEFAULT_MIN_SIZE
        self.max_size = max_size or gear.DEFAULT_MAX_SIZE
        self.shared = shared

    def open_layer(self, out: BinaryIO,
                   backend_id: str | None = None) -> LayerSink:
        from makisu_tpu.chunker.cdc import ChunkSession
        service = None
        if self.shared:
            from makisu_tpu.chunker.service import shared_service
            service = shared_service()
        session = ChunkSession(self.avg_bits, self.min_size,
                               self.max_size, service=service)
        if _use_native(out, backend_id):
            # Native pipeline + chunker tap: one pass does tar framing,
            # digests, gzip (C++) AND CDC intake (device).
            return NativeLayerSink(out, backend_id=backend_id,
                                   session=session)
        return _TPUSink(out, session, backend_id=backend_id)


def get_hasher(name: str) -> Hasher:
    import os
    if name == "cpu":
        return CPUHasher()
    if name == "tpu":
        # Worker mode sets MAKISU_TPU_SHARED_HASH so concurrent builds
        # batch onto the shared device stream.
        return TPUHasher(
            shared=os.environ.get("MAKISU_TPU_SHARED_HASH") == "1")
    raise ValueError(f"unknown hasher {name!r} (choose cpu or tpu)")
