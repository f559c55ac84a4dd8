"""Layer model: the set of changes one build step commits.

A layer maps logical paths to entries — file content (with its on-disk
source for tar streaming) or whiteouts (deletions). Committing writes
entries to a tar stream in sorted path order, which both makes layer bytes
deterministic and groups whiteouts with their siblings.

Reference capability: lib/snapshot/mem_layer.go (contentMemFile,
whiteoutMemFile, addHeader/addWhiteout/rangeFiles).
"""

from __future__ import annotations

import dataclasses
import os
import tarfile
import threading
import time

from makisu_tpu import tario
from makisu_tpu.snapshot.walk import WHITEOUT_PREFIX
from makisu_tpu.utils import concurrency, metrics, pathutils


@dataclasses.dataclass
class ContentEntry:
    """A file/dir/symlink present in the layer; content streams from
    ``src`` on disk at commit time."""

    src: str
    dst: str  # logical absolute path; layer key
    hdr: tarfile.TarInfo

    def commit(self, tw: tarfile.TarFile,
               data: bytes | None = None) -> None:
        tario.write_entry(tw, self.src, self.hdr, data=data)

    def header(self) -> tuple[tarfile.TarInfo, str | None]:
        """The entry's tar header, and the file its content streams
        from where it has any (``tario.write_entry``'s rule)."""
        has_content = self.hdr.isreg() and self.hdr.size > 0
        return self.hdr, self.src if has_content else None


@dataclasses.dataclass
class WhiteoutEntry:
    """A deletion: commits as an empty ``.wh.<name>`` marker."""

    deleted: str  # logical absolute path being deleted; layer key

    def commit(self, tw: tarfile.TarFile,
               data: bytes | None = None) -> None:
        tw.addfile(self.header()[0])

    def header(self) -> tuple[tarfile.TarInfo, None]:
        d, b = os.path.split(self.deleted)
        return tarfile.TarInfo(
            pathutils.rel_path(os.path.join(d, WHITEOUT_PREFIX + b))), None


class _ReadAhead:
    """File read-ahead for a Python tar writer: upcoming ContentEntry
    bytes prefetch on the commit pool, off the (strictly ordered)
    writer's thread, and are handed to the writer directly, so it never
    blocks on a cold page-cache read. (The native sink takes entries by
    the batch and reads ahead on threads of its own.)

    Prefetch results are advisory: any read error, or a file whose size
    changed since its header was recorded, yields ``None`` and the
    writer falls back to streaming from disk, which surfaces errors
    through the exact same code path as the serial commit. In-flight
    bytes are budgeted so a layer of large files can't balloon memory.
    """

    MAX_FILE_BYTES = 8 * 1024 * 1024   # larger files stream as before
    BUDGET_BYTES = 64 * 1024 * 1024    # in-flight prefetch cap

    def __init__(self, items: list[tuple[str, "ContentEntry"]],
                 workers: int) -> None:
        self._queue = list(items)  # (key, entry), commit order
        self._queue.reverse()      # pop() from the front cheaply
        self._pool = concurrency.hash_pool()
        # Bounded by TASKS as well as bytes: a layer of 50k tiny files
        # must not enqueue 50k reads ahead of the SHA/scan stages on
        # the shared FIFO pool (bulk read-ahead would effectively
        # serialize hashing behind it).
        self._max_tasks = max(4 * workers, 8)
        self._futs: dict[str, tuple] = {}  # key -> (future, size)
        self._inflight = 0
        self._lock = threading.Lock()
        self._busy = [0.0]  # worker read seconds (flushed at close)
        self._top_up()

    def _top_up(self) -> None:
        while (self._queue and self._inflight < self.BUDGET_BYTES
               and len(self._futs) < self._max_tasks):
            key, entry = self._queue.pop()
            size = entry.hdr.size
            self._inflight += size
            self._futs[key] = (concurrency.submit_ctx(
                self._pool, self._read, entry.src, size), size)
        metrics.stage_queue_depth("read_ahead", len(self._futs))

    def _read(self, src: str, size: int) -> bytes | None:
        t0 = time.monotonic()
        try:
            with open(src, "rb") as f:
                data = f.read(size + 1)
        except OSError:
            return None  # writer re-reads and surfaces the real error
        finally:
            with self._lock:
                self._busy[0] += time.monotonic() - t0
        # A size change since the scan means the header no longer
        # matches the content; the streaming path owns that failure
        # mode (tarfile raises on short reads), so fall back to it.
        return data if len(data) == size else None

    def take(self, key: str) -> bytes | None:
        """Prefetched bytes for ``key``, else None. Tops the pipeline
        back up as the writer consumes entries."""
        fut, size = self._futs.pop(key, (None, 0))
        if fut is None:
            return None
        self._inflight -= size
        self._top_up()
        try:
            data = fut.result()
        except Exception:  # noqa: BLE001 - advisory stage
            return None
        return data

    def close(self) -> None:
        # Cancel what never started: orphaned reads would otherwise
        # occupy pool slots ahead of the next layer's scan/SHA tasks
        # (already-running reads finish on their own, harmlessly).
        for fut, _ in self._futs.values():
            fut.cancel()
        self._futs.clear()
        self._queue = []
        metrics.stage_busy_add("read_ahead", self._busy[0])
        metrics.stage_queue_depth("read_ahead", 0)


class Layer:
    """Ordered path → entry map for one committed layer."""

    def __init__(self) -> None:
        self.entries: dict[str, ContentEntry | WhiteoutEntry] = {}

    def __len__(self) -> int:
        return len(self.entries)

    def add_header(self, src: str, dst: str,
                   hdr: tarfile.TarInfo) -> ContentEntry | WhiteoutEntry:
        """Record a content entry (or a whiteout, if dst basename carries
        the whiteout prefix — as found in pulled layer tars)."""
        dst = pathutils.abs_path(dst)
        d, b = os.path.split(dst)
        if b.startswith(WHITEOUT_PREFIX):
            entry = WhiteoutEntry(os.path.join(d, b[len(WHITEOUT_PREFIX):]))
            self.entries[entry.deleted] = entry
        else:
            entry = ContentEntry(src, dst, hdr)
            self.entries[dst] = entry
        return entry

    def add_whiteout(self, deleted: str) -> WhiteoutEntry:
        deleted = pathutils.abs_path(deleted)
        if os.path.basename(deleted).startswith(WHITEOUT_PREFIX):
            raise ValueError(f"path already carries whiteout prefix: {deleted}")
        entry = WhiteoutEntry(deleted)
        self.entries[deleted] = entry
        return entry

    def kind_counts(self) -> dict[str, int]:
        """Entries by kind, as the tar will hold them: ``file``,
        ``dir``, ``symlink``, ``whiteout``, ``other`` (hard links,
        devices, fifos)."""
        counts: dict[str, int] = {}
        for entry in self.entries.values():
            if isinstance(entry, WhiteoutEntry):
                kind = "whiteout"
            elif entry.hdr.isreg():
                kind = "file"
            elif entry.hdr.isdir():
                kind = "dir"
            elif entry.hdr.issym():
                kind = "symlink"
            else:
                kind = "other"
            counts[kind] = counts.get(kind, 0) + 1
        return counts

    # A batch handed to a native writer in one call: few enough entries
    # that the progress stamp, a tap's error and an abort stay timely,
    # and no more content than the sink's read-ahead ring holds.
    _BATCH_ENTRIES = 256
    _BATCH_BYTES = 16 * 1024 * 1024

    def commit(self, tw: tarfile.TarFile,
               workers: int | None = None) -> None:
        """Write entries in sorted path order (cache-identity-bearing).
        The native sink's writer takes them by the batch
        (``add_entries``), whiteouts and header-only entries in their
        sorted place, and reads the files ahead itself. A
        ``tarfile.TarFile`` (the Python sink) takes them one by one;
        with ``workers > 1`` (default: concurrency.hash_workers), file
        content prefetches ahead of it on the commit pool. The produced
        tar bytes are identical either way."""
        keys = sorted(self.entries)
        if not isinstance(tw, tarfile.TarFile):
            batch, content = [], 0
            for key in keys:
                hdr, src = item = self.entries[key].header()
                batch.append(item)
                content += hdr.size if src is not None else 0
                if (len(batch) == self._BATCH_ENTRIES
                        or content >= self._BATCH_BYTES):
                    tw.add_entries(batch)
                    batch, content = [], 0
            if batch:
                tw.add_entries(batch)
            return
        if workers is None:
            workers = concurrency.hash_workers()
        ra = None
        if workers > 1:
            eligible = [
                (k, e) for k in keys
                if isinstance(e := self.entries[k], ContentEntry)
                and e.hdr.isreg()
                and 0 < e.hdr.size <= _ReadAhead.MAX_FILE_BYTES]
            if len(eligible) > 1:
                ra = _ReadAhead(eligible, workers=workers)
        try:
            for key in keys:
                data = ra.take(key) if ra is not None else None
                self.entries[key].commit(tw, data=data)
        finally:
            if ra is not None:
                ra.close()
