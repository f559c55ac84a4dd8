"""Layer-tar I/O: deterministic gzip, header apply/compare/write.

Reference capability: lib/tario/ (gzip levels gzip.go:26-47, ApplyHeader
apply.go:26, IsSimilarHeader compare.go:24-104, WriteEntry write.go:28,
untar untar.go:33). Python's tarfile.TarInfo is the header record
throughout the framework.

Determinism note: gzip output is part of a layer's registry identity, so
the writer pins mtime=0 and omits the filename — identical tar bytes at the
same compression level always produce identical gzip bytes.
"""

from __future__ import annotations

import contextlib
import gzip
import hashlib
import io
import os
import sys
import tarfile
import zlib
from typing import BinaryIO


class TeeDigest:
    """File-like fanning writes to a sha256 digest and an underlying
    file (the commit pipeline's gzip-digest tap and chunk
    reconstitution both hash-while-writing through this)."""

    def __init__(self, out: BinaryIO) -> None:
        self.out = out
        self.digest = hashlib.sha256()
        self.size = 0

    def write(self, data: bytes) -> int:
        self.digest.update(data)
        self.size += len(data)
        return self.out.write(data)

    def flush(self) -> None:
        self.out.flush()

# Compression levels mirror the reference's flag surface
# (no/speed/default/size → tario.CompressionLevel, gzip.go:26-47).
COMPRESSION_LEVELS = {"no": 0, "speed": 1, "default": 6, "size": 9}

_compression_level = COMPRESSION_LEVELS["default"]

# Compressor backend: "zlib" (stdlib, one continuous deflate stream —
# inherently serial: its bytes are cache identity and a continuous
# stream cannot be split across lanes) or "pgzip" (blockwise deflate —
# the reference's multicore pgzip capability; block-parallel via
# BlockGzipWriter on the shared hash pool, native/pgzip.cpp providing
# the fast codec). Both are deterministic, but produce different
# bytes, so the backend id is part of a layer's cache identity (cache
# entries record it; chunk reconstitution replays with the same
# backend).
_gzip_backend = "zlib"
_PGZIP_BLOCK = 128 * 1024


def _validate_backend(name: str) -> None:
    # pgzip no longer requires the native library: the block format is
    # reproducible by the stdlib zlib codec (byte-identical slices, see
    # _py_deflate_blocks), so any host can WRITE and REPLAY pgzip ids —
    # the native entry points are a throughput route, not a capability.
    # ``auto`` still resolves to zlib on lib-less hosts (the Python
    # codec is correct but not the speed pick; see resolve_backend).
    if name not in ("zlib", "pgzip"):
        raise ValueError(f"unknown gzip backend {name!r}")


def set_gzip_backend(name: str) -> None:
    global _gzip_backend
    _validate_backend(name)
    _gzip_backend = name


def gzip_backend_id(level: int | None = None,
                    backend: str | None = None) -> str:
    """The single format site for backend-id strings (cache identity:
    recorded in cache entries, parsed back by gzip_writer)."""
    level = _compression_level if level is None else level
    backend = _gzip_backend if backend is None else backend
    if backend == "pgzip":
        return f"pgzip-{level}-{_PGZIP_BLOCK}"
    return f"zlib-{level}"


def parse_backend_id(backend_id: str) -> tuple[str, int, int]:
    """THE parse of the backend-id wire format — ``zlib-<level>`` or
    ``pgzip-<level>-<block>`` — shared by acceptance
    (backend_id_usable) and replay (gzip_writer) so the two can never
    drift: an id accepted at pull time is definitionally one replay can
    parse. Raises ValueError on malformed or out-of-range ids; returns
    (backend, level, block) with block 0 for zlib."""
    parts = backend_id.split("-")
    _validate_backend(parts[0])
    level = int(parts[1])
    if not 0 <= level <= 9:  # zlib's valid level range
        raise ValueError(f"gzip level {level} out of range in "
                         f"{backend_id!r}")
    block = 0
    if parts[0] == "pgzip":
        block = int(parts[2])
        if block <= 0:
            raise ValueError(f"pgzip block {block} invalid in "
                             f"{backend_id!r}")
    return parts[0], level, block


def backend_id_usable(backend_id: str | None) -> bool:
    """True when a recorded backend id can be replayed by gzip_writer in
    THIS process — known backend name, well-formed level/block. Every
    host can replay both backends now (the pgzip block format has a
    stdlib-zlib codec, byte-identical to the native one), so this
    reduces to well-formedness; cache routes that promise future
    reconstitution (chunk dedup's lazy hits) still consult it so a
    MALFORMED or future-versioned id degrades to the blob route at pull
    time, not to a failed build at export time. ``None`` (legacy entry
    with no recorded identity) is NOT replayable: the producing
    settings are unknown, so a byte-identical rebuild cannot be
    promised."""
    if backend_id is None:
        return False
    try:
        parse_backend_id(backend_id)
    except (ValueError, IndexError):
        return False
    return True


def resolve_backend(name: str) -> str:
    """Resolve the flag-level backend choice to a concrete backend:
    ``auto`` takes pgzip (parallel block deflate) when
    native/libpgzip.so is loadable, else zlib. Only CONCRETE backends
    ever appear in backend-id strings — cache identity records what a
    blob was actually compressed with, never the policy that chose
    it."""
    if name != "auto":
        return name
    from makisu_tpu.native import pgzip_available
    return "pgzip" if pgzip_available() else "zlib"


def make_backend_id(backend: str, level_name: str) -> str:
    """Validate a (backend, level) flag pair into a backend id string —
    the per-build compression identity threaded through BuildContext, so
    concurrent builds with different flags never race on the module
    globals (those remain only as process defaults). Accepts ``auto``
    (resolved here via resolve_backend)."""
    backend = resolve_backend(backend)
    _validate_backend(backend)
    if level_name not in COMPRESSION_LEVELS:
        raise ValueError(
            f"invalid compression level {level_name!r}; "
            f"one of {sorted(COMPRESSION_LEVELS)}")
    return gzip_backend_id(COMPRESSION_LEVELS[level_name], backend)


def set_compression(name: str) -> None:
    global _compression_level
    try:
        _compression_level = COMPRESSION_LEVELS[name]
    except KeyError:
        raise ValueError(
            f"invalid compression level {name!r}; "
            f"one of {sorted(COMPRESSION_LEVELS)}") from None


def compression_level() -> int:
    return _compression_level


class _BlockBuffer:
    """Fixed-granularity re-blocking: the determinism contract shared
    by the level-0 stored-block writer and the block-parallel compress
    stage. Compressed output that depends on input call sizes (zlib
    level-0 stored-block framing; pgzip's per-block slices) becomes a
    pure function of content once the compressor is fed in exactly
    ``granularity``-sized blocks, regardless of who writes (tarfile's
    ~16KiB writes vs reconstitution's single whole-layer write)."""

    def __init__(self, granularity: int) -> None:
        if granularity <= 0:
            raise ValueError("granularity must be positive")
        self.granularity = granularity
        self._buf = bytearray()

    def feed(self, data) -> list[bytes]:
        """Absorb ``data``; return the complete blocks now available."""
        self._buf += data
        g = self.granularity
        blocks = []
        while len(self._buf) >= g:
            blocks.append(bytes(self._buf[:g]))
            del self._buf[:g]
        return blocks

    def tail(self) -> bytes:
        """Drain the final partial block (stream end)."""
        t = bytes(self._buf)
        self._buf.clear()
        return t


class _FixedGranularityWriter:
    """Re-buffers writes into fixed-size blocks before the compressor
    (the zlib level-0 stored-block determinism fix; see _BlockBuffer).
    """

    GRANULARITY = 64 * 1024

    def __init__(self, gz) -> None:
        self._gz = gz
        self._blocks = _BlockBuffer(self.GRANULARITY)

    def write(self, data: bytes) -> int:
        for block in self._blocks.feed(data):
            self._gz.write(block)
        return len(data)

    def close(self) -> None:
        tail = self._blocks.tail()
        if tail:
            self._gz.write(tail)
        self._gz.close()

    def flush(self) -> None:  # pragma: no cover - parity shim
        pass

    def __enter__(self) -> "_FixedGranularityWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# Gzip member header for the pgzip block format (mirrors the native
# side's kPgzipHeader: deflate, no flags, mtime 0, XFL 0, OS 255).
_PGZIP_HEADER = bytes([0x1f, 0x8b, 0x08, 0, 0, 0, 0, 0, 0, 0xff])


def _py_deflate_blocks(data: bytes, level: int, block_size: int,
                       last: bool) -> bytes:
    """Pure-Python codec for the pgzip block format: compress ``data``
    as consecutive ``block_size`` raw-deflate slices, each sync-flush
    terminated; a final batch (``last``) additionally emits the tail
    ``len(data) % block_size`` bytes — possibly empty — as the Z_FINISH
    slice (the exact streaming convention PgzipWriter/layersink.cpp
    shipped; blob cache identity). Byte-identical to native
    ``DeflateSlice`` concatenation — both drive the same zlib with the
    same parameters (windowBits -15, memLevel 8, default strategy),
    asserted by tests. This is what makes pgzip backend ids replayable
    on hosts without the native library."""
    import zlib
    n = len(data)
    nfull = n // block_size
    if not last and nfull * block_size != n:
        raise ValueError("non-final batches must be whole blocks")
    nblocks = nfull + 1 if last else nfull
    if nblocks == 0:
        raise ValueError("empty non-final batch")
    out = []
    for i in range(nblocks):
        co = zlib.compressobj(level, zlib.DEFLATED, -15, 8,
                              zlib.Z_DEFAULT_STRATEGY)
        piece = co.compress(data[i * block_size:(i + 1) * block_size])
        fin = last and i + 1 == nblocks
        piece += co.flush(zlib.Z_FINISH if fin else zlib.Z_SYNC_FLUSH)
        out.append(piece)
    return b"".join(out)


def _deflate_blocks(data: bytes, level: int, block_size: int,
                    last: bool) -> bytes:
    """One batch of pgzip blocks: native multi-block entry when the
    library is there (one GIL-released call), stdlib zlib otherwise —
    identical bytes either way."""
    from makisu_tpu import native
    if native.pgzip_available():
        return native.deflate_blocks(data, level, block_size, last)
    return _py_deflate_blocks(data, level, block_size, last)


class BlockGzipWriter:
    """Block-parallel deterministic gzip writer (the commit pipeline's
    compress stage for the pgzip backend).

    Input re-blocks through :class:`_BlockBuffer` into ``block_size``
    slices; batches of blocks compress concurrently on the shared
    ``concurrency.hash_pool()`` (each batch one GIL-released native
    call — or the stdlib codec, byte-identical) and stitch back in
    stream order. Output is a single gzip member, a pure function of
    (content, level, block_size): identical at every worker count and
    identical to ``native.pgzip_compress`` / the native layersink's
    pgzip route. ``workers`` defaults to the context's
    ``compress_workers()``; 1 compresses inline (no pool).

    Busy seconds land on the ``compress`` stage counter from the lane
    tasks themselves (``reports_compress_busy`` tells LayerSink's feed
    thread not to double-count its cheap buffering writes)."""

    # Blocks per lane task: batches amortize call overhead while one
    # batch stays a bounded slice of memory (~1MiB at the 128KiB
    # default block).
    BATCH_BLOCKS = 8
    reports_compress_busy = True

    def __init__(self, fileobj: BinaryIO, level: int = 6,
                 block_size: int = _PGZIP_BLOCK,
                 workers: int | None = None) -> None:
        import zlib
        from makisu_tpu.utils import concurrency
        self._out = fileobj
        self._level = level
        self._block = block_size
        self._blocks = _BlockBuffer(block_size)
        self._crc = zlib.crc32(b"")
        self._size = 0
        if workers is None:
            workers = concurrency.compress_workers()
        self._workers = max(1, workers)
        self._pool = concurrency.hash_pool() if self._workers > 1 \
            else None
        self._batch: list[bytes] = []   # whole blocks awaiting a lane
        self._pending: list = []        # ordered lane futures
        self._submits = 0               # queue-depth sampling stride
        self._closed = False
        self._out.write(_PGZIP_HEADER)

    def _compress_task(self, payload: bytes, last: bool) -> bytes:
        import time as _time
        from makisu_tpu.utils import metrics
        t0 = _time.monotonic()
        try:
            return _deflate_blocks(payload, self._level, self._block,
                                   last)
        finally:
            metrics.stage_busy_add(metrics.COMPRESS_STAGE,
                                   _time.monotonic() - t0)
            nblocks = len(payload) // self._block + (1 if last else 0)
            metrics.counter_add(metrics.COMPRESS_BLOCKS, nblocks,
                                backend="pgzip")

    def _submit(self, payload: bytes, last: bool) -> None:
        if self._pool is None:
            # Inline lane: identical bytes, no pool round trip.
            self._out.write(self._compress_task(payload, last))
            return
        from makisu_tpu.utils import concurrency, metrics
        self._pending.append(concurrency.submit_ctx(
            self._pool, self._compress_task, payload, last))
        self._submits += 1
        if not self._submits & 0x0F:
            metrics.stage_queue_depth(metrics.COMPRESS_STAGE,
                                      len(self._pending))
        # Bound in-flight batches: each lane may own one plus one
        # queued — the stage's memory ceiling, and the backpressure
        # that keeps a fast producer from flooding the shared pool.
        while len(self._pending) > 2 * self._workers:
            self._out.write(self._pending.pop(0).result())
        # Opportunistically retire completed fronts without blocking.
        while self._pending and self._pending[0].done():
            self._out.write(self._pending.pop(0).result())

    def _flush_batch(self, last: bool) -> None:
        if self._batch or last:
            self._submit(b"".join(self._batch), last)
            self._batch = []

    def write(self, data: bytes) -> int:
        import zlib
        self._crc = zlib.crc32(data, self._crc)
        self._size += len(data)
        for block in self._blocks.feed(data):
            self._batch.append(block)
            if len(self._batch) >= self.BATCH_BLOCKS:
                self._flush_batch(last=False)
        return len(data)

    def flush(self) -> None:
        self._out.flush()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._batch.append(self._blocks.tail())
        self._flush_batch(last=True)
        for fut in self._pending:
            self._out.write(fut.result())
        self._pending = []
        trailer = (self._crc & 0xFFFFFFFF).to_bytes(4, "little") + \
            (self._size & 0xFFFFFFFF).to_bytes(4, "little")
        self._out.write(trailer)

    def __enter__(self) -> "BlockGzipWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def gzip_writer(fileobj: BinaryIO, level: int | None = None,
                backend_id: str | None = None):
    """Deterministic gzip writer. ``backend_id`` (from a cache entry)
    forces a specific backend/level/block so reconstituted bytes match."""
    level = _compression_level if level is None else level
    backend = _gzip_backend
    block = _PGZIP_BLOCK
    if backend_id is not None:
        backend, level, parsed_block = parse_backend_id(backend_id)
        if backend == "pgzip":
            block = parsed_block
    if backend == "pgzip":
        return BlockGzipWriter(fileobj, level=level, block_size=block)
    gz = gzip.GzipFile(fileobj=fileobj, mode="wb", compresslevel=level,
                       mtime=0, filename="")
    if level == 0:
        # Stored-block framing is write-granularity-dependent; pin it.
        return _FixedGranularityWriter(gz)
    return gz


class BlockInflater:
    """A gzip blob's inflated stream, read forward a block at a time:
    the one reader of a gzip layer blob (a cached layer's parse,
    index_layer's pass). zlib inflates each block with the GIL free and
    verifies a member's trailer (CRC32, ISIZE) as it reads the member's
    last byte; a read slices the block it falls in and never inflates
    less than a block, a forward seek drops whole blocks unsliced, and
    ``finish`` inflates whatever is left, so every byte is inflated
    once whoever wanted it. What follows a member's trailer is read as
    ``GzipFile`` reads it: zero padding is passed over, anything else
    is a further member of the same stream (or fails as one).

    File-like as far as ``tarfile`` mode ``"r:"`` asks: ``read``,
    ``tell``, ``seek`` forward, and back by at most ``TAIL`` bytes
    before the current block (``TarFile.next`` steps back one byte to
    see that a member's body was all there)."""

    READ = 1 << 20   # compressed bytes a read
    BLOCK = 4 << 20  # inflated bytes a block, at most
    TAIL = 512       # bytes kept of the block before

    def __init__(self, raw) -> None:
        self._raw = raw
        self._z = zlib.decompressobj(31)
        self._block = b""
        self._start = 0  # stream offset of _block[0]
        self._tail = b""
        self._pos = 0
        self.reads = 0   # decompress calls made

    def _next(self) -> bool:
        """Step to the next block; False at the stream's end."""
        if self._block:
            self._tail = self._block[-self.TAIL:]
        self._start += len(self._block)
        self._block = b""
        while not self._block:
            if self._z.eof:
                pending = self._z.unused_data.lstrip(b"\0")
                while not pending:
                    pending = self._raw.read(self.READ)
                    if not pending:
                        return False
                    pending = pending.lstrip(b"\0")
                self._z = zlib.decompressobj(31)
            else:
                pending = (self._z.unconsumed_tail
                           or self._raw.read(self.READ))
                if not pending:
                    raise EOFError("layer blob ended before its gzip "
                                   "stream's end-of-stream marker")
            self.reads += 1
            try:
                self._block = self._z.decompress(pending, self.BLOCK)
            except zlib.error as e:
                # The class GzipFile gave a bad header or trailer.
                raise gzip.BadGzipFile(str(e)) from e
        return True

    def read(self, n: int = -1) -> bytes:
        """Up to ``n`` bytes from the position on (all that is left
        without ``n``); fewer only at the stream's end."""
        if n is None or n < 0:
            n = sys.maxsize
        parts: list[bytes] = []
        while n > 0:
            lo = self._pos - self._start
            if lo < 0:
                piece = self._tail[lo:][:n]
            else:
                if lo >= len(self._block):
                    if not self._next():
                        break
                    continue
                piece = self._block[lo:lo + n]
            parts.append(piece)
            self._pos += len(piece)
            n -= len(piece)
        return parts[0] if len(parts) == 1 else b"".join(parts)

    def tell(self) -> int:
        return self._pos

    def seek(self, offset: int, whence: int = io.SEEK_SET) -> int:
        """Forward for free: the blocks passed over are dropped by the
        next read. Back as far as the kept tail reaches."""
        if whence != io.SEEK_SET:
            raise io.UnsupportedOperation("seek from the start only")
        if offset < self._start - len(self._tail):
            raise io.UnsupportedOperation(
                f"seek back to {offset}, block starts at {self._start}")
        self._pos = offset
        return offset

    def take(self, offset: int, length: int) -> bytes:
        """The stream's bytes [offset, offset + length). Offsets never
        go back; blocks wholly before ``offset`` are dropped unsliced."""
        self.seek(offset)
        data = self.read(length)
        if len(data) < length:
            raise ValueError(f"layer stream ended at {self._pos}, "
                             f"chunk needs {offset + length}")
        return data

    def finish(self) -> int:
        """Inflate to the blob's end; returns the stream's length."""
        while self._next():
            pass
        return self._start

    def close(self) -> None:
        self._block = self._tail = b""

    def __enter__(self) -> "BlockInflater":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def gzip_reader(fileobj: BinaryIO):
    """Layer-blob reader: gzip by default (``BlockInflater``),
    transparently zstd when the blob's frame magic says so
    (zstd-published base images reach every apply/extract/diff site
    through this one function). Unseekable inputs keep the legacy
    gzip-only path — every layer-blob call site hands in a real file,
    and a wrong guess on an exotic stream must not break it."""
    try:
        pos = fileobj.tell()
        head = fileobj.read(4)
        fileobj.seek(pos)
    except (OSError, AttributeError):
        return gzip.GzipFile(fileobj=fileobj, mode="rb")
    from makisu_tpu.utils import zstdio
    if zstdio.is_zstd(head):
        return zstdio.ZstdReader(fileobj)
    return BlockInflater(fileobj)


@contextlib.contextmanager
def layer_tar(stream):
    """The parse of a layer's tar stream, as every apply site opens it.
    Over a ``BlockInflater`` the members are read from its blocks
    (mode ``"r:"``: a body nobody unpacks is sought past, one that is
    unpacked is sliced from the block), and once the last member is
    parsed the blob is inflated to its end, so every member's trailer
    is verified before the apply counts. Any other stream (zstd, the
    chunk route) is read forward in mode ``"r|"``."""
    blocks = isinstance(stream, BlockInflater)
    with tarfile.open(fileobj=stream, mode="r:" if blocks else "r|") as tf:
        yield tf
    if blocks:
        stream.finish()


def is_similar_header(h: tarfile.TarInfo, nh: tarfile.TarInfo,
                      ignore_time: bool = False) -> bool:
    """Structural equality by file type — the cheap "did this change?"
    predicate behind both the scan diff and untar short-circuiting.

    Regular files compare (mtime, uid, gid, size, mode); directories and
    hardlinks the same minus/plus size/linkname; symlinks compare the link
    target only. mtimes compare at 1-second granularity (tar's resolution).
    """
    if not h.name and not nh.name:
        return True  # "/" itself is never modified
    if h.issym():
        return nh.issym() and h.linkname == nh.linkname
    time_ok = ignore_time or int(h.mtime) == int(nh.mtime)
    if h.islnk():
        return (nh.islnk() and time_ok and h.linkname == nh.linkname
                and h.uid == nh.uid and h.gid == nh.gid and h.mode == nh.mode)
    if h.isdir():
        return (nh.isdir() and time_ok and h.uid == nh.uid
                and h.gid == nh.gid and h.mode == nh.mode)
    if h.isreg():
        return (nh.isreg() and time_ok and h.uid == nh.uid and h.gid == nh.gid
                and h.size == nh.size and h.mode == nh.mode)
    raise ValueError(f"unsupported tar entry type {h.type!r} for {h.name}")


def apply_header(path: str, h: tarfile.TarInfo) -> None:
    """Apply header metadata (mode/owner/mtime) to an on-disk path."""
    if not h.issym():
        os.chmod(path, h.mode)
    try:
        os.lchown(path, h.uid, h.gid)
    except PermissionError:
        pass  # unprivileged runs keep the current owner
    if not h.issym():
        os.utime(path, (h.mtime, h.mtime))


def apply_header_fd(fd: int, h: tarfile.TarInfo) -> None:
    """:func:`apply_header` for a regular file through its open
    descriptor, after the last write: the same three settings with no
    path walked for them."""
    os.fchmod(fd, h.mode)
    try:
        os.fchown(fd, h.uid, h.gid)
    except PermissionError:
        pass  # unprivileged runs keep the current owner
    os.utime(fd, (h.mtime, h.mtime))


def write_entry(tw, src: str, h: tarfile.TarInfo,
                data: bytes | None = None) -> None:
    """Write one entry into a ``tarfile.TarFile``; regular-file
    content streams from ``src``. ``data`` is the read-ahead pool's
    prefetched content (exactly ``h.size`` bytes,
    snapshot/layer._ReadAhead): byte-identical to the disk read, minus
    the cold-cache stall on the writer's thread."""
    if h.isreg() and h.size > 0:
        if data is not None and len(data) == h.size:
            import io
            tw.addfile(h, io.BytesIO(data))
            return
        with open(src, "rb") as f:
            tw.addfile(h, f)
    else:
        tw.addfile(h)


def untar(tf: tarfile.TarFile, dest: str) -> None:
    """Plain untar into dest (no whiteout handling; reference untar.go:33).

    Uses the stdlib "tar" extraction filter: absolute names and
    parent-escaping paths in hostile tars are rejected rather than
    written outside ``dest``.
    """
    for member in tf:
        tf.extract(member, dest, filter="tar")
