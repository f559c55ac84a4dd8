"""Thread-safe content-addressed store with LRU eviction.

Reference capabilities covered: lib/storage/layer_tar_store.go (CAS by hex
digest, download→cache state transition, hardlink in/out, LRU 256) and the
generic machinery under lib/storage/base/ (atomic state transitions,
last-access tracking, sharded dirs). Implementation is original: one class,
atomic os.rename commits out of a staging directory, one mutex around the
in-memory recency map, eviction by persisted last-access time.

This module owns the on-disk layout (``<root>/<aa>/<name>``, staging
under ``<root>/_tmp/``): nothing else in the program builds an entry's
path or lists a CAS directory. ``CASDir`` is the layout alone, usable on
a store no process has open; ``CASStore`` adds recency, the entry cap
and pins; ``store_for(root)`` hands out whichever this process has.
"""

from __future__ import annotations

import itertools
import os
import shutil
import tempfile
import threading
import time
from typing import BinaryIO, Callable, Iterable, Iterator

from makisu_tpu.utils import pathutils

_SHARD_CHARS = 2


class _FdWriter:
    """The ``write`` half of a file object over a raw descriptor: what
    ``write_file``'s callback needs, without ``os.fdopen``'s ``fstat``
    and buffer (every call here is a round trip on a network file
    system, and a chunk is one ``write``)."""

    def __init__(self, fd: int) -> None:
        self._fd = fd

    def write(self, data) -> int:
        view = memoryview(data).cast("B")
        total = len(view)
        while view:
            view = view[os.write(self._fd, view):]
        return total


class CASDir:
    """The layout of one CAS directory: ``root/<aa>/<name>``, entries
    staged under ``root/_tmp/`` and renamed into place. It keeps no
    recency and enforces no cap, so it serves a directory no store in
    this process has open (census, scrub, the evictor, tier refetch).

    Constructing one touches nothing on disk, and reading through one
    never creates or lists the root: ``walk`` yields nothing for a root
    that is not there, skips a shard that is deleted under it and an
    entry that vanishes before its ``stat``. ``put`` makes what it
    needs the first time it is refused. No method hands out an entry's
    path; ``where`` names the place for a finding a person will read.

    **Ingest sequence** (every path): create ``_tmp/<name>.<pid>.<n>``
    with ``O_CREAT|O_EXCL``, write, close, rename onto ``<aa>/<name>``.
    The staging name is unique per process and call, so nothing probes
    for it and it is unlinked only when the sequence fails. A shard
    directory is made the first time this handle meets it (``_shards``);
    a rename that reports ``ENOENT`` (someone removed the directory)
    makes it again and retries once.
    """

    def __init__(self, root: str) -> None:
        self.root = root
        self._tmp_dir = os.path.join(root, "_tmp")
        # Shard directories this handle has made or seen.
        self._shards: set[str] = set()
        self._stage_seq = itertools.count()

    def _path(self, name: str) -> str:
        shard = name[:_SHARD_CHARS] if len(name) > _SHARD_CHARS else "__"
        return os.path.join(self.root, shard, name)

    def where(self, name: str) -> str:
        """The entry's place, for a finding or a log line: to be read,
        never opened."""
        return self._path(name)

    # What a store with a recency map does on access, on commit and on
    # delete; a bare directory has none to keep.

    def _touch(self, name: str) -> None:
        pass

    def _admit(self, names: Iterable[str]) -> None:
        pass

    def _forget(self, name: str) -> None:
        pass

    def recency(self) -> dict[str, float]:
        """Last-access times this process has seen, by name: none for a
        bare directory, whose file mtimes are all there is."""
        return {}

    def seed_state(self) -> dict | None:
        """None: no recency map to seed (mtimes on disk are complete)."""
        return None

    # -- queries ----------------------------------------------------------

    def _entries(self) -> Iterator[os.DirEntry]:
        """Every directory entry under a shard, staging left out."""
        try:
            shards = os.scandir(self.root)
        except OSError:
            return
        with shards:
            for shard in shards:
                if shard.name == "_tmp" or not shard.is_dir():
                    continue
                try:
                    entries = os.scandir(shard.path)
                except OSError:
                    continue  # shard deleted under us
                with entries:
                    yield from entries

    def walk(self) -> Iterator[tuple[str, int, float]]:
        """``(name, size, mtime)`` of every committed entry: one
        ``stat`` each, never a staging file."""
        for entry in self._entries():
            try:
                st = entry.stat()
            except OSError:
                continue  # deleted under us
            if entry.is_file():
                yield entry.name, st.st_size, st.st_mtime

    def keys(self) -> list[str]:
        return [entry.name for entry in self._entries()]

    def open(self, name: str) -> BinaryIO:
        """Open for reading: ONE syscall on the happy path (the open
        itself is the existence check) — this runs once per ~8KiB chunk
        when a layer applies straight from the chunk CAS, so a
        stat-then-open here is a measurable tax at 100k chunks."""
        try:
            f = open(self._path(name), "rb")
        except FileNotFoundError:
            raise FileNotFoundError(
                f"{name} not in store {self.root}") from None
        self._touch(name)
        return f

    def read(self, name: str) -> bytes:
        with self.open(name) as f:
            return f.read()

    # -- ingest -----------------------------------------------------------

    def _stage_path(self, name: str) -> str:
        """A staging path no other call can hold: the entry's name, this
        process, a counter (``next`` on a count is atomic)."""
        return os.path.join(
            self._tmp_dir,
            f"{name}.{os.getpid()}.{next(self._stage_seq)}")

    def _stage(self, name: str, write: Callable[[BinaryIO], None]) -> str:
        tmp = self._stage_path(name)
        flags = os.O_CREAT | os.O_EXCL | os.O_WRONLY
        try:
            fd = os.open(tmp, flags, 0o600)
        except FileNotFoundError:
            # A bare directory nobody has written through yet.
            os.makedirs(self._tmp_dir, exist_ok=True)
            fd = os.open(tmp, flags, 0o600)
        try:
            try:
                write(_FdWriter(fd))
            finally:
                os.close(fd)
        except BaseException:
            self._remove(tmp)
            raise
        return tmp

    @staticmethod
    def _remove(path: str) -> None:
        """Unlink; a file that is not there is fine."""
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass

    def _rename(self, tmp: str, dst: str) -> None:
        """Move a staged file onto its final path; on failure the
        staging file is removed and nothing shows under the name."""
        sharddir = os.path.dirname(dst)
        shard = os.path.basename(sharddir)
        try:
            if shard not in self._shards:
                try:
                    os.mkdir(sharddir)
                except FileExistsError:
                    pass
                self._shards.add(shard)
            try:
                os.rename(tmp, dst)
            except FileNotFoundError:
                # The shard directory went away under the memo.
                os.makedirs(sharddir, exist_ok=True)
                os.rename(tmp, dst)
        except BaseException:
            self._remove(tmp)
            raise

    def write_many(self, items: Iterable[tuple[str, bytes]]) -> None:
        """Bulk ingest of entries whose name is the digest of their
        bytes (the caller has verified it, and has probed that they are
        new). Four file-system calls an entry — create, write, close,
        rename — and one lock round for the batch. No ``stat`` of the
        final path: identical bytes under one name make a rename over a
        racing writer's file the same outcome as losing to it. The
        first failure is raised after the entries already renamed are
        recorded; a failed entry leaves nothing behind."""
        done: list[str] = []
        try:
            for name, data in items:
                self._rename(self._stage(
                    name, lambda f, data=data: f.write(data)),
                    self._path(name))
                done.append(name)
        finally:
            if done:
                self._admit(done)

    def put(self, name: str, data: bytes) -> None:
        """One entry through ``write_many``: ``name`` is the digest of
        ``data``, and the caller has checked it."""
        self.write_many(((name, data),))

    def delete(self, name: str) -> None:
        self._remove(self._path(name))
        self._forget(name)


class CASStore(CASDir):
    """A ``CASDir`` a process has open: recency, an entry cap, pins.

    Names are arbitrary keys (layer hex digests in practice). Files land via
    ``write_file``/``link_file``/``write_many``, always committed with an
    atomic rename so readers never observe partial content. ``max_entries``
    bounds the store; least-recently-used entries are evicted on overflow.
    The root and its staging directory are made, and the root listed
    once for the shards already there, when the store is opened.

    **Who wins a race.** ``write_file``/``write_bytes``/``link_file``
    take arbitrary names and keep first-writer-wins: one ``stat`` of the
    final path before the rename, an existing entry stays. ``write_many``
    takes entries whose name is the caller-verified digest of their
    bytes and issues no such ``stat``: two writers of one name hold
    identical bytes, so a rename over a racing writer's file leaves
    exactly what "first writer wins" would have left.

    **The lock** guards the recency map (``_last_access``) and eviction,
    nothing else. It is never held across a system call in ``exists``,
    ``size``, ``path``, ``open`` or any ingest path; eviction alone
    unlinks under it, so a victim is chosen and removed as one step.
    """

    # Stores below this cap seed their LRU map eagerly at construction
    # (a few hundred stats); at or above it — the ~1M-entry chunk CAS,
    # where the seed scan is tens of thousands of stats and was a
    # measurable warm-rebuild floor term — seeding runs on a background
    # thread armed by the first write, and eviction simply defers until
    # the scan lands (advisory LRU: a few deferred evictions cost disk
    # headroom, never correctness).
    _EAGER_SEED_BELOW = 4096

    def __init__(self, root: str, max_entries: int = 256) -> None:
        super().__init__(root)
        self.max_entries = max_entries
        self._lock = threading.Lock()
        self._last_access: dict[str, float] = {}
        # Optional pin predicate (name -> bool). A True answer keeps
        # the entry out of count-LRU victim selection — the content
        # store's refcount plane wires this so an in-flight read can
        # never lose its chunk to the entry-count cap either.
        self.pin_check = None
        os.makedirs(root, exist_ok=True)
        os.makedirs(self._tmp_dir, exist_ok=True)
        # One listdir here instead of a makedirs per commit.
        self._shards = set(os.listdir(root))
        self._seeded = False
        self._seeding = False
        if max_entries < self._EAGER_SEED_BELOW:
            for name, _, mtime in self.walk():
                self._last_access[name] = mtime
            self._seeded = True

    def _seed_async_locked(self) -> None:
        """Arm the background LRU seed (large stores). Runs at most
        once; merges on-disk mtimes under the lock, live accesses
        recorded meanwhile win, then catches up deferred eviction."""
        if self._seeded or self._seeding:
            return
        self._seeding = True

        def run() -> None:
            seed: dict[str, float] = {}
            try:
                for name, _, mtime in self.walk():
                    seed[name] = mtime
            finally:
                with self._lock:
                    for name, mtime in seed.items():
                        self._last_access.setdefault(name, mtime)
                    self._seeded = True
                    self._seeding = False
                    self._evict_locked()

        threading.Thread(target=run, daemon=True,
                         name="cas-lru-seed").start()

    def seed_state(self) -> dict:
        """Observability for the background LRU seed (PR 10's thread
        is otherwise invisible): ``state`` is ``seeded`` (recency map
        complete), ``seeding`` (scan in flight), or ``unseeded``
        (large store, seed not yet armed — it arms on first write).
        Consumers that rank objects by recency (the storage plane's
        eviction dry-run) refuse to run unless ``seeded``."""
        with self._lock:
            if self._seeded:
                state = "seeded"
            elif self._seeding:
                state = "seeding"
            else:
                state = "unseeded"
            return {"state": state,
                    "seeded_entries": len(self._last_access)}

    def _touch(self, name: str) -> None:
        with self._lock:
            self._last_access[name] = time.time()

    def _admit(self, names: Iterable[str]) -> None:
        """Record committed entries and evict the overflow: one lock
        round per call, however many names."""
        now = time.time()
        with self._lock:
            for name in names:
                self._last_access[name] = now
            self._evict_locked()

    def _forget(self, name: str) -> None:
        with self._lock:
            self._last_access.pop(name, None)

    def recency(self) -> dict[str, float]:
        with self._lock:
            return dict(self._last_access)

    # -- queries ----------------------------------------------------------

    def exists(self, name: str) -> bool:
        if os.path.isfile(self._path(name)):
            self._touch(name)
            return True
        return False

    def size(self, name: str) -> int:
        size = os.path.getsize(self._path(name))  # raises if absent
        self._touch(name)
        return size

    # -- ingest -----------------------------------------------------------

    def _commit(self, name: str, tmp: str) -> str:
        """First writer wins (names here are arbitrary keys): an entry
        that is already there stays, and the staged file goes."""
        dst = self._path(name)
        if os.path.isfile(dst):
            self._remove(tmp)
            self._touch(name)
            return dst
        self._rename(tmp, dst)
        self._admit((name,))
        return dst

    def write_file(self, name: str, write: Callable[[BinaryIO], None]) -> str:
        """Stream content into the store via ``write(fileobj)``; atomic."""
        return self._commit(name, self._stage(name, write))

    def write_bytes(self, name: str, data: bytes) -> str:
        return self.write_file(name, lambda f: f.write(data))

    def link_file(self, name: str, src: str) -> str:
        """Ingest an existing file by hardlink (falls back to copy across
        filesystems)."""
        tmp = self._stage_path(name)
        try:
            try:
                os.link(src, tmp)
            except OSError:
                shutil.copy2(src, tmp)
        except BaseException:
            self._remove(tmp)
            raise
        return self._commit(name, tmp)

    def mkstemp(self, prefix: str) -> tuple[int, str]:
        """``tempfile.mkstemp`` in the staging directory: beside the
        entries (one file system, so a ``link_file`` of the result can
        hardlink) and out of every walk. The caller owns the file."""
        return tempfile.mkstemp(prefix=prefix, dir=self._tmp_dir)

    # -- egress -----------------------------------------------------------

    def path(self, name: str) -> str:
        """Path of a stored file (raises FileNotFoundError if absent)."""
        p = self._path(name)
        if not os.path.isfile(p):
            raise FileNotFoundError(f"{name} not in store {self.root}")
        self._touch(name)
        return p

    def link_out(self, name: str, dst: str) -> None:
        """Hardlink a stored file out to ``dst`` (copy across filesystems)."""
        src = self.path(name)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        if os.path.exists(dst):
            os.unlink(dst)
        try:
            os.link(src, dst)
        except OSError:
            shutil.copy2(src, dst)

    # -- eviction ---------------------------------------------------------

    def _evict_locked(self) -> None:
        """Evict the LRU overflow. Large stores (the ~1M-entry chunk
        CAS) evict in 10% batches: a min() scan per insert is O(n), and
        at a few hundred thousand entries the store would spend its
        time scanning access times, not storing bytes. Small stores
        (the 256-entry layer-blob cache, where every entry is a warm
        multi-hundred-MB blob) evict exactly the excess — dumping 10%
        of THOSE would force re-pulls the old one-at-a-time policy
        never did."""
        import heapq
        if not self._seeded:
            # LRU state still loading (large store, background seed):
            # defer — the seed's completion re-runs this.
            self._seed_async_locked()
            return
        if len(self._last_access) <= self.max_entries:
            return
        pool = self._last_access
        if self.pin_check is not None:
            try:
                pool = {name: ts for name, ts in
                        self._last_access.items()
                        if not self.pin_check(name)}
            # Pins advise; a broken pin_check must never block eviction,
            # so fall back to the full pool.  # check: allow(silent-swallow)
            except Exception:  # noqa: BLE001
                pool = self._last_access
        excess = len(self._last_access) - self.max_entries
        batch = excess if self.max_entries < 4096 else max(
            excess, self.max_entries // 10)
        batch = min(batch, len(pool))
        victims = heapq.nsmallest(batch, pool, key=pool.get)
        for victim in victims:
            p = self._path(victim)
            if os.path.isfile(p):
                os.unlink(p)
            del self._last_access[victim]


# -- the stores this process has open ----------------------------------------

# Live stores by real path of their root. A store is entered by whoever
# opens it to serve (a build's chunk dedup, the worker's serve plane);
# a root entered again replaces its entry, so the map is bounded by the
# storage roots the process has built against, not by its builds.
_live: dict[str, CASStore] = {}
_live_lock = threading.Lock()


def register_live(store: CASStore) -> None:
    with _live_lock:
        _live[pathutils.real_path(store.root)] = store


def live_stores(roots=None) -> list[CASStore]:
    """The live stores, or those among ``roots`` (real paths)."""
    with _live_lock:
        return [store for root, store in _live.items()
                if roots is None or root in roots]


def store_for(root: str) -> CASDir:
    """The store for this root: the live ``CASStore`` where this
    process has one open (its recency then hears of what is read, put
    and deleted), else the bare directory."""
    with _live_lock:
        live = _live.get(pathutils.real_path(root))
    return live if live is not None else CASDir(root)
