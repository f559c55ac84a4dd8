"""Resident build sessions: keep a context's expensive warm state alive
across builds so the warm path is actually warm.

Every rebuild used to pay full startup, a complete context re-scan, and
re-chunking of untouched regions even when the worker process never
died (ROADMAP item 5). A **build session** — keyed by context path +
the resolved flag identity — keeps resident, per context:

- the stat/content-ID cache (``utils/statcache.ContentIDCache``): no
  JSON reload of 100k entries per build;
- the context-scan memo: per ADD/COPY source subtree, the cache-ID
  checksum transition ``(source, checksum_in) → checksum_out`` — an
  untouched subtree's contribution replays in O(1) with zero syscalls;
- the MemFS layer-replay memo: the header sequence of every applied
  layer keyed by blob digest, so a cached layer folds into the MemFS
  tree without re-inflating the blob or re-parsing the tar;
- the dirty-set tracker: an inotify watcher (ctypes, Linux) with a
  portable mtime-walk delta fallback (``snapshot.walk.snapshot_delta``)
  accumulating changed paths between builds.

The resolved native/JAX runtime stays resident for free (the worker is
one process); the session records its identity so an ISA/ABI flip
invalidates rather than silently mixing routes.

Invalidation story (every reason labels
``makisu_session_invalidations_total``):

- ``flag_identity``: same context, different resolved build flags;
- ``isa_change``: the native ISA/ABI route moved under the process;
- ``ttl``: idle beyond ``MAKISU_TPU_SESSION_TTL`` seconds;
- ``lru``: evicted past ``MAKISU_TPU_SESSION_MAX`` sessions or the
  ``MAKISU_TPU_SESSION_MAX_MB`` resident-byte budget (accounted on
  ``/healthz``);
- ``explicit``: ``POST /sessions/invalidate`` or a manager reset.

Correctness contract: a session only ever REPLAYS state that is a pure
function of inputs that provably didn't change (stat signatures with
the racily-clean discipline, digest-keyed layer headers), so image
digests are byte-identical to a cold build at every point — asserted
by the dirty-set tests and the ``northstar_incremental`` bench.
"""

from __future__ import annotations

import contextvars
import ctypes
import ctypes.util
import hashlib
import json
import os
import struct
import threading
import time

import importlib

from makisu_tpu.utils import ledger, metrics
from makisu_tpu.utils import logging as log
from makisu_tpu.utils import pathutils

# The snapshot package re-exports the walk FUNCTION under the module's
# own name; resolve the MODULE explicitly.
walk_mod = importlib.import_module("makisu_tpu.snapshot.walk")

# Session metric names live in the utils/metrics.py registry (the
# `check` metric-registry invariant: one spelling per series).
SESSION_HITS = metrics.SESSION_HITS
SESSION_INVALIDATIONS = metrics.SESSION_INVALIDATIONS
SESSION_RESIDENT_BYTES = metrics.SESSION_RESIDENT_BYTES

# Rough per-unit resident-byte estimates for the /healthz accounting.
# Exact sizes would need sys.getsizeof walks per build; the budget is a
# safety cap, not a ledger, so stable estimates beat precise churn.
_BYTES_PER_LAYER_ENTRY = 600   # TarInfo + path strings
_BYTES_PER_CONTENT_ID = 200    # statcache entry (key + stat quadruple)
_BYTES_PER_MEMO = 160          # scan-memo key/value

# Scan-memo entries kept per session: keys are (source, checksum_in);
# upstream cache-ID churn mints new keys, so stale ones age out by cap.
_SCAN_MEMO_KEEP = 512


def enabled() -> bool:
    """Resident sessions are on by default (a session that is never
    reused costs one dict entry); MAKISU_TPU_SESSION=0 disables."""
    return os.environ.get("MAKISU_TPU_SESSION", "1") == "1"


def session_ttl() -> float:
    try:
        return float(os.environ.get("MAKISU_TPU_SESSION_TTL", "3600"))
    except ValueError:
        return 3600.0


def max_sessions() -> int:
    try:
        return int(os.environ.get("MAKISU_TPU_SESSION_MAX", "8"))
    except ValueError:
        return 8


def max_resident_bytes() -> int:
    try:
        mb = float(os.environ.get("MAKISU_TPU_SESSION_MAX_MB", "512"))
    except ValueError:
        mb = 512.0
    return int(mb * 1e6)


def max_watches() -> int:
    try:
        return int(os.environ.get("MAKISU_TPU_SESSION_MAX_WATCHES",
                                  "8192"))
    except ValueError:
        return 8192


# This build's residency state for the history record's ``warm_mode``
# label: "resident" (session reused with an exact dirty set), "fresh"
# (new session: first build of this context/identity), "rescan"
# (session reused but dirty knowledge was lost — full re-scan), "off"
# (sessions disabled or bypassed), "none" (non-build command).
_warm_mode: "contextvars.ContextVar[str]" = contextvars.ContextVar(
    "makisu_session_warm_mode", default="none")


def warm_mode() -> str:
    return _warm_mode.get()


def set_warm_mode(label: str) -> None:
    _warm_mode.set(label)


def _isa_identity() -> str:
    """The native route identity a session was built under. Only what
    is ALREADY resolved: sessions must not force a native-library load
    (cheap commands never pay `make`)."""
    from makisu_tpu import native
    return native.isa_route_if_resolved() or "unresolved"


def _identity_dict(args, gzip_backend_id: str) -> dict:
    return {
        "context": os.path.abspath(args.context),
        "root": os.path.abspath(args.root),
        "dockerfile": os.path.abspath(
            args.file or os.path.join(args.context, "Dockerfile")),
        "hasher": args.hasher,
        "gzip_backend_id": gzip_backend_id,
        "modifyfs": bool(args.modifyfs),
        "commit": args.commit,
        "target": args.target,
        "build_args": sorted(args.build_arg),
        "blacklist": sorted(args.blacklist),
    }


def _digest_identity(ident: dict) -> str:
    blob = json.dumps(ident, sort_keys=True,
                      separators=(",", ":")).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def identity_from_build_args(args, storage_dir: str,
                             gzip_backend_id: str) -> str:
    """Stable digest of the resolved flags that shape build identity
    for one context. Anything here that moves mints a new session
    (reason=flag_identity) — mixing, say, two hashers' warm state
    would be silently wrong."""
    ident = _identity_dict(args, gzip_backend_id)
    ident["storage"] = os.path.abspath(storage_dir)
    return _digest_identity(ident)


def portable_identity_from_build_args(args,
                                      gzip_backend_id: str) -> str:
    """The flag identity MINUS the storage dir: the fleet front door
    rewrites ``--storage`` per worker, so the full identity of one
    logical build differs across workers. Session snapshots key and
    validate on this portable form — everything that shapes build
    OUTPUT is still in it, only the machine-local storage location is
    not (a restored memo never depends on where chunks happen to
    live)."""
    return _digest_identity(_identity_dict(args, gzip_backend_id))


def snapshot_policy() -> str:
    """MAKISU_TPU_SESSION_SNAPSHOT: "1" checkpoints every successful
    build, "0" disables the snapshot plane entirely, default "auto"
    checkpoints only residency-hinted sessions (worker / --watch /
    repeat builds) — a one-shot CLI build on a cold host skips the
    serialization it could never redeem."""
    return os.environ.get("MAKISU_TPU_SESSION_SNAPSHOT", "auto")


# -- inotify watcher --------------------------------------------------------

_IN_ACCESS = 0x00000001
_IN_MODIFY = 0x00000002
_IN_ATTRIB = 0x00000004
_IN_CLOSE_WRITE = 0x00000008
_IN_MOVED_FROM = 0x00000040
_IN_MOVED_TO = 0x00000080
_IN_CREATE = 0x00000100
_IN_DELETE = 0x00000200
_IN_DELETE_SELF = 0x00000400
_IN_MOVE_SELF = 0x00000800
_IN_ISDIR = 0x40000000
_IN_Q_OVERFLOW = 0x00004000
_IN_IGNORED = 0x00008000
_IN_NONBLOCK = 0x00000800  # O_NONBLOCK on linux
_IN_CLOEXEC = 0x00080000   # O_CLOEXEC on linux

_WATCH_MASK = (_IN_MODIFY | _IN_ATTRIB | _IN_CLOSE_WRITE
               | _IN_MOVED_FROM | _IN_MOVED_TO | _IN_CREATE
               | _IN_DELETE | _IN_DELETE_SELF | _IN_MOVE_SELF)

_EVENT_HDR = struct.Struct("iIII")  # wd, mask, cookie, len


def _libc():
    name = ctypes.util.find_library("c")
    return ctypes.CDLL(name, use_errno=True) if name else None


class InotifyWatcher:
    """Recursive inotify watch over a context tree. Best-effort by
    design: any failure (no inotify, watch-limit ENOSPC, queue
    overflow, structural events that stale the wd→path map) flips
    ``healthy`` off and the session falls back to the mtime-walk
    delta. ``collect()`` drains pending events into a dirty-path set;
    ``resync()`` (after a build) re-registers watches so directories
    created between builds are covered going forward."""

    def __init__(self, root: str, blacklist: list[str]) -> None:
        self.root = root
        self.blacklist = list(blacklist)
        self.healthy = False
        self._fd = -1
        self._wd_paths: dict[int, str] = {}
        self._needs_resync = False
        self._libc = _libc()
        if self._libc is None or not hasattr(self._libc,
                                             "inotify_init1"):
            return
        fd = self._libc.inotify_init1(_IN_NONBLOCK | _IN_CLOEXEC)
        if fd < 0:
            return
        self._fd = fd
        self.healthy = self._add_watches()
        if not self.healthy:
            self.close()

    def _dirs(self) -> list[str]:
        """Directory list via a stat-free descent (dirent type bits
        only, a directory read whole by one call where the native
        reader is built: ``walk.child_dirs``): registering watches over
        a 100k-file tree must not pay a full per-file lstat walk."""
        from makisu_tpu.utils import pathutils
        dirs = [self.root]
        stack = [self.root]
        limit = max_watches()
        try:
            while stack:
                cur = stack.pop()
                for name in walk_mod.child_dirs(cur):
                    path = os.path.join(cur, name)
                    if pathutils.is_descendant_of_any(
                            path, self.blacklist):
                        continue
                    dirs.append(path)
                    if len(dirs) > limit:
                        return dirs  # caller sees > cap and bails
                    stack.append(path)
        except OSError:
            return []
        return dirs

    def _add_watches(self) -> bool:
        dirs = self._dirs()
        if not dirs or len(dirs) > max_watches():
            return False
        for path in dirs:
            wd = self._libc.inotify_add_watch(
                self._fd, path.encode(), _WATCH_MASK)
            if wd < 0:
                return False  # ENOSPC / vanished dir: fall back whole
            self._wd_paths[wd] = path
        return True

    def collect(self) -> set[str] | None:
        """Drain events into dirty paths. ``None`` means knowledge was
        lost (overflow, read error, structural staleness) — callers
        must fall back to a full re-scan."""
        if not self.healthy:
            return None
        dirty: set[str] = set()
        structural = False
        while True:
            try:
                buf = os.read(self._fd, 65536)
            except BlockingIOError:
                break
            except OSError:
                self.healthy = False
                return None
            if not buf:
                break
            off = 0
            while off + _EVENT_HDR.size <= len(buf):
                wd, mask, _cookie, nlen = _EVENT_HDR.unpack_from(
                    buf, off)
                name = buf[off + _EVENT_HDR.size:
                           off + _EVENT_HDR.size + nlen].rstrip(b"\0")
                off += _EVENT_HDR.size + nlen
                if mask & _IN_Q_OVERFLOW:
                    self.healthy = False
                    return None
                base = self._wd_paths.get(wd)
                if mask & _IN_IGNORED:
                    self._wd_paths.pop(wd, None)
                    structural = True
                    continue
                if base is None:
                    continue
                path = (os.path.join(base, name.decode(
                    errors="surrogateescape")) if name else base)
                dirty.add(path)
                if mask & (_IN_ISDIR | _IN_DELETE_SELF
                           | _IN_MOVE_SELF):
                    # A directory appeared/vanished/moved: its
                    # subtree's future events are unreliable until
                    # watches re-register (resync after the build).
                    # The dir itself is dirty, which forces the
                    # containing source to re-walk — correctness holds
                    # without per-event watch surgery.
                    structural = True
        if structural:
            self._needs_resync = True
        return dirty

    def resync(self) -> None:
        """Re-register watches after structural churn (directory
        create/delete/rename staled the wd→path map or left subtrees
        unwatched). NO-OP on the steady path: without a structural
        event no new directories can exist, so a stable tree pays
        nothing per build — the per-build full-tree walk this replaces
        was itself a warm-floor term at 100k files."""
        if not self.healthy or not self._needs_resync:
            return
        with metrics.span("session_resync") as sp:
            for wd in list(self._wd_paths):
                self._libc.inotify_rm_watch(self._fd, wd)
            self._wd_paths.clear()
            self._needs_resync = False
            self.healthy = self._add_watches()
            sp.set(watches=len(self._wd_paths))

    def close(self) -> None:
        if self._fd >= 0:
            try:
                os.close(self._fd)
            except OSError:
                pass
            self._fd = -1
        self.healthy = False


# -- the session ------------------------------------------------------------


class BuildSession:
    """One context's resident warm state. Single-writer: the manager
    hands a session to at most one build at a time (concurrent builds
    of the same context bypass with reason=busy)."""

    def __init__(self, context_dir: str, identity: str) -> None:
        self.context_dir = context_dir
        self.identity = identity
        self.isa = _isa_identity()
        self.created_mono = time.monotonic()
        self.last_used_mono = self.created_mono
        self.builds = 0
        self.hits = 0
        self.busy = False
        # Resident state.
        self.content_ids = None  # adopted from the first BuildContext
        self.scan_memo: dict[tuple[str, int],
                             tuple[int, int, int]] = {}
        # Applied-layer op streams keyed by (applied-chain, digest):
        # valid only at the exact chain position they were recorded at
        # (builder/node.py holds the correctness argument).
        self.layer_replay: dict[tuple[str, str], list] = {}
        self._layer_entry_count = 0
        self.snapshot: walk_mod.TreeSnapshot | None = None
        self.watcher: InotifyWatcher | None = None
        self.pending_dirty: set[str] = set()
        # True iff the dirty set provably covers every change since the
        # last successful build; False forces a full re-scan.
        self.exact = False
        self._ignore_sig = None  # .dockerignore content hash
        self._walk_blacklist: list[str] = []
        # Whether arming expensive tracking (the full-walk baseline)
        # is worth it: set per build from resident_process / repeat use.
        self._resident_hint = False
        # -- session-snapshot plane (worker/snapshots.py) --
        # The portable flag identity + storage dir arrive with the
        # lease; without them the snapshot plane stays dark.
        self.portable_identity: str | None = None
        self.storage_dir: str | None = None
        # True for the first build after a snapshot restore: reported
        # as warm_mode=restored. The companion flag below survives
        # until the first release(), where a byte-budget eviction the
        # restore caused labels lru_restore instead of plain lru.
        self.restored = False
        self._restore_fresh = False
        # Restored stat-cache entries, merged into the context's
        # content-ID cache at the next begin_build (the cache instance
        # doesn't exist until a build arrives).
        self._restored_stat_entries: dict | None = None
        # A restored walk baseline certifies a PAST point; the next
        # poll must delta against it once before trusting the watcher.
        self._gap_delta_pending = False
        # Incremental-write bookkeeping: previous checkpoint's shard
        # chunks (carry-forward), dirty flags per shard family, and the
        # watcher-mode persistence baseline (the live watcher session
        # needs no walk; snapshots do).
        self._snap_shards: dict[str, dict] = {}
        self._snap_scan_dirty = True
        self._snap_stat_all = True
        self._snap_walk_dirty: set[str] = set()
        self._snap_walk_all = True
        self._snap_baseline: walk_mod.TreeSnapshot | None = None
        self._snap_gap_paths = 0
        # The running build's listing of the context tree
        # (``BuildContext.listing``), held from begin_build to the end
        # of finish_build so that the checkpoint's baseline walk
        # replays the build's stats; None between builds.
        self.build_listing: walk_mod.TreeListing | None = None

    # -- accounting --

    def resident_bytes(self) -> int:
        n = self._layer_entry_count * _BYTES_PER_LAYER_ENTRY
        n += len(self.scan_memo) * _BYTES_PER_MEMO
        if self.content_ids is not None:
            n += (len(getattr(self.content_ids, "_entries", None) or ())
                  * _BYTES_PER_CONTENT_ID)
        if self.snapshot is not None:
            n += self.snapshot.approx_bytes()
        return n

    def stats(self) -> dict:
        now = time.monotonic()
        return {
            "context": self.context_dir,
            "identity": self.identity,
            "isa": self.isa,
            "builds": self.builds,
            "hits": self.hits,
            "resident_bytes": self.resident_bytes(),
            "layers_cached": len(self.layer_replay),
            "scan_memo_entries": len(self.scan_memo),
            "dirty_pending": len(self.pending_dirty),
            "dirty_exact": self.exact,
            "watcher": ("inotify" if self.watcher is not None
                        and self.watcher.healthy else "mtime-walk"),
            "age_seconds": round(now - self.created_mono, 3),
            "idle_seconds": round(now - self.last_used_mono, 3),
            "busy": self.busy,
        }

    # -- dirty tracking --

    def _ignore_signature(self):
        path = os.path.join(self.context_dir, ".dockerignore")
        try:
            with open(path, "rb") as f:
                return hashlib.sha256(f.read()).hexdigest()
        except OSError:
            return None

    def poll_changes(self) -> set[str]:
        """Accumulate changes since the last poll/build into
        ``pending_dirty`` and return the signature-confirmed NEW dirt
        from this poll (what a watch loop triggers on). Watcher events
        when healthy; one mtime-walk delta otherwise.

        Knowledge loss — watcher overflow/death, a failed delta walk,
        or no baseline at all — NEVER goes silent: the session turns
        inexact, the whole context is flagged dirty once (so the next
        build re-scans everything and a watch loop rebuilds), and a
        fresh walk baseline is seeded so tracking resumes."""
        gap_dirty: set[str] = set()
        if self._gap_delta_pending and self.snapshot is not None:
            # Restored session: the persisted baseline certifies the
            # state at snapshot time — one delta against it surfaces
            # everything that moved in the snapshot→restore gap at the
            # same trust level the live mtime-walk fallback has. Only
            # after it runs may a (freshly created, gap-blind) watcher
            # be believed.
            self._gap_delta_pending = False
            try:
                self.snapshot, delta = walk_mod.snapshot_delta(
                    self.snapshot, self._walk_blacklist)
            except OSError:
                self.snapshot = None
                self.exact = False
                self.pending_dirty.add(self.context_dir)
                self._snap_walk_all = True
                return {self.context_dir}
            gap_dirty = delta.dirty
            self.pending_dirty |= gap_dirty
            self._snap_walk_dirty |= gap_dirty
        if self.watcher is not None and self.watcher.healthy:
            got = self.watcher.collect()
            if got is not None:
                self.pending_dirty |= got
                self._snap_gap_paths += len(got)
                # New dirs appeared? Register their watches BEFORE the
                # caller scans, so edits inside them during the build
                # are evented (no-op without structural churn).
                self.watcher.resync()
                if self.watcher.healthy:
                    return got | gap_dirty
            # Overflow / read error / resync failure: the watcher is
            # dead — release its fd + kernel watches (a long-lived
            # worker must not pin inotify limits on corpses) and fall
            # through to re-seed the walk baseline.
            self.watcher.close()
        if self.snapshot is not None:
            try:
                self.snapshot, delta = walk_mod.snapshot_delta(
                    self.snapshot, self._walk_blacklist)
            except OSError:
                self.snapshot = None
                self.exact = False
                self.pending_dirty.add(self.context_dir)
                self._snap_walk_all = True
                return {self.context_dir}
            self.pending_dirty |= delta.dirty
            self._snap_walk_dirty |= delta.dirty
            return delta.real_dirty | gap_dirty
        # No baseline: what changed since the last certified point is
        # unknowable — flag everything once and re-baseline. The
        # baseline walk (a full lstat pass) only runs when residency
        # can pay it back: a resident process, or an in-process repeat
        # build. A one-shot CLI build on a watcher-less host skips it
        # — it would be a 100k-file walk armed for a process about to
        # exit.
        self.exact = False
        self.pending_dirty.add(self.context_dir)
        if self._resident_hint:
            try:
                self.snapshot = walk_mod.snapshot_tree(
                    self.context_dir, self._walk_blacklist)
            except OSError:
                self.snapshot = None
        return {self.context_dir}

    # -- build lifecycle --

    def begin_build(self, ctx, resident_process: bool = False) -> str:
        """Arm ``ctx`` with this session's resident state. Returns the
        warm mode this build runs under ("resident" | "rescan").
        ``resident_process`` (worker / --watch) additionally defers
        statcache persistence to a background thread — a one-shot CLI
        process must keep the synchronous save or it may exit before
        the write lands."""
        with metrics.span("session_begin") as sp:
            mode = self._begin_build(ctx, resident_process)
            sp.set(mode=mode, dirty=len(ctx.dirty_paths))
        metrics.counter_add(metrics.SESSION_DIRTY_PATHS,
                            len(ctx.dirty_paths))
        return mode

    def _begin_build(self, ctx, resident_process: bool) -> str:
        self.builds += 1
        self.last_used_mono = time.monotonic()
        self._resident_hint = resident_process or self.builds >= 2
        self.storage_dir = ctx.image_store.root
        self._walk_blacklist = [
            p for p in (list(ctx.base_blacklist)
                        + [ctx.image_store.root])
            if p != ctx.context_dir]
        # The tracker must exist BEFORE this build's scan reads any
        # file: an edit landing mid-build (after the scan passed it)
        # must surface in the NEXT build's dirty set — watcher events
        # queue in the kernel; the walk baseline below is captured
        # pre-scan so the next delta re-examines anything that moved
        # after it. A baseline taken after the build would absorb
        # mid-build edits and replay a stale scan memo.
        if self.watcher is None:
            self.watcher = InotifyWatcher(self.context_dir,
                                          self._walk_blacklist)
            if not self.watcher.healthy:
                self.watcher.close()
        self.poll_changes()
        # .dockerignore governs which paths enter cache identity but
        # lives OUTSIDE the per-source subtrees, so the scan memo can't
        # see it change through the dirty containment check — hash it
        # every build and drop the memo on any change.
        ignore_sig = self._ignore_signature()
        if ignore_sig != self._ignore_sig:
            if self._ignore_sig is not None or ignore_sig is not None:
                self.scan_memo.clear()
                self._snap_scan_dirty = True
            self._ignore_sig = ignore_sig
        # Adopt or install the resident content-ID cache.
        if self.content_ids is None:
            self.content_ids = ctx.content_ids
        else:
            ctx.content_ids = self.content_ids
        # Snapshot-restored stat entries merge on first use —
        # setdefault semantics (local knowledge wins), and every
        # adopted entry still faces the per-lookup stat comparison and
        # racily-clean window, so a stale restored entry re-hashes
        # instead of replaying.
        if self._restored_stat_entries is not None:
            merge = getattr(self.content_ids, "merge_entries", None)
            if merge is not None:
                merge(self._restored_stat_entries)
            self._restored_stat_entries = None
        begin = getattr(self.content_ids, "begin_build", None)
        if begin is not None:
            begin()
        # Resident process: the statcache's disk copy is durability
        # only — persist it off the build's critical path.
        if resident_process:
            self.content_ids.defer_save = True
        mode = "resident" if self.exact else "rescan"
        if self.restored:
            # First build after a snapshot restore: same residency
            # semantics as the mode it shadows (dirty_exact still
            # gates the scan memo), but reported distinctly so the
            # fleet can tell a hand-off from a resident hit.
            mode = "restored"
            self.restored = False
        ctx.session = self
        ctx.dirty_paths = frozenset(self.pending_dirty)
        ctx.dirty_exact = self.exact
        self.build_listing = ctx.listing
        if self.exact:
            self.hits += 1
            metrics.counter_add(SESSION_HITS)
        log.info("build session %s: mode=%s dirty=%d builds=%d",
                 self.identity, mode, len(self.pending_dirty),
                 self.builds)
        return mode

    def finish_build(self, ctx, ok: bool) -> None:
        # Watcher drain and resync, then the session's checkpoint.
        with metrics.span("session_finish", ok=ok):
            self._finish_build(ctx, ok)

    def _finish_build(self, ctx, ok: bool) -> None:
        self.last_used_mono = time.monotonic()
        if ok:
            # Everything dirty was consumed by this build's scan.
            self.pending_dirty.clear()
            if self.watcher is not None and self.watcher.healthy:
                # Mid-build edits are drained AND kept pending: the
                # scan may have read a file before the racing write
                # landed — one conservative extra re-hash, never a
                # stale identity. Collect runs BEFORE resync so a
                # raced structural event (new dir) triggers the watch
                # rebuild.
                raced = self.watcher.collect()
                self.watcher.resync()
                if raced is None or not self.watcher.healthy:
                    # Watcher died at the finish line: the next
                    # begin's poll flags the context and re-seeds a
                    # walk baseline.
                    self.watcher.close()
                    self.snapshot = None
                    self.exact = False
                else:
                    self.pending_dirty |= raced
                    self._snap_gap_paths += len(raced)
                    self.exact = True
            else:
                # mtime-walk fallback: the baseline captured at
                # begin_build — BEFORE this build's scan — is the
                # certification point; the next delta re-examines
                # anything that moved after it, including mid-build
                # edits.
                self.exact = self.snapshot is not None
        else:
            # A failed build may have consumed part of the dirty set
            # before dying; only a full re-scan re-certifies it.
            self.exact = False
            self.snapshot = None
            self.pending_dirty.clear()
            self.scan_memo.clear()
            self._snap_scan_dirty = True
            self._snap_walk_all = True
        # The per-build context must not leak a dead session reference,
        # nor the session the build's listing past its checkpoint.
        ctx.session = None
        ctx.dirty_paths = frozenset()
        ctx.dirty_exact = False
        try:
            if ok:
                self.checkpoint()
        finally:
            self.build_listing = None

    def checkpoint(self, force: bool = False) -> dict | None:
        """Write this session's snapshot through the chunk CAS
        (worker/snapshots.py). Incremental — only dirty shards
        re-chunk — and advisory: any failure costs durability, never
        the build. ``force`` (the worker's POST /sessions/snapshot and
        the drain hand-off) checkpoints even sessions the auto policy
        would skip."""
        policy = snapshot_policy()
        if policy == "0" or not self.portable_identity \
                or not self.storage_dir:
            return None
        if not force and policy == "auto" and not self._resident_hint:
            return None
        from makisu_tpu.worker import snapshots as snapshots_mod
        recipe = snapshots_mod.write_snapshot(self, self.storage_dir)
        mgr = manager()
        if recipe is None:
            mgr.note_snapshot("write_error",
                              context=self.context_dir)
        else:
            mgr.note_snapshot("write", context=self.context_dir)
        return recipe

    # -- memo surfaces (called via ctx by steps/memfs/node) --

    def scan_lookup(self, source: str, checksum_in: int):
        key = (source, checksum_in)
        hit = self.scan_memo.get(key)
        if hit is not None:
            # Recency bump (dict insertion order IS the LRU order): a
            # hot key replayed every build must not be evicted by a
            # burst of one-shot keys that arrived after it.
            self.scan_memo.pop(key)
            self.scan_memo[key] = hit
        return hit

    def scan_store(self, source: str, checksum_in: int,
                   checksum_out: int, files: int, nbytes: int) -> None:
        if len(self.scan_memo) >= _SCAN_MEMO_KEEP:
            # Recency-order eviction: the front of the dict is the
            # least recently stored OR replayed key (scan_lookup
            # re-inserts on hit), so stale keys from superseded chains
            # age out first and hot keys survive one-shot bursts.
            self.scan_memo.pop(next(iter(self.scan_memo)))
        self.scan_memo[(source, checksum_in)] = (
            checksum_out, files, nbytes)
        self._snap_scan_dirty = True

    def replay_lookup(self, key: tuple[str, str]):
        return self.layer_replay.get(key)

    def replay_store(self, key: tuple[str, str],
                     entries: list) -> None:
        if key in self.layer_replay:
            return
        self.layer_replay[key] = entries
        self._layer_entry_count += len(entries)

    def evict_layers(self, keep_bytes: int) -> None:
        """Drop oldest layer memos until resident bytes fit."""
        while (self.layer_replay
               and self.resident_bytes() > keep_bytes):
            key, entries = next(iter(self.layer_replay.items()))
            del self.layer_replay[key]
            self._layer_entry_count -= len(entries)

    def close(self) -> None:
        if self.watcher is not None:
            self.watcher.close()
            self.watcher = None


# -- the manager ------------------------------------------------------------


class SessionManager:
    """Process-wide session registry with TTL/LRU/byte-budget
    eviction. One session per context path; acquire is non-blocking —
    a second concurrent build of the same context bypasses residency
    instead of serializing on it."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._sessions: dict[str, BuildSession] = {}
        self.invalidations: dict[str, int] = {}
        # Snapshot-plane accounting (durable for the life of the
        # worker, unlike the event-bus ledger): what /healthz exports
        # and `doctor --fleet`'s snapshot_restore_failed finding reads.
        self.snapshot_counts: dict[str, int] = {}
        self.last_restore_failure: dict = {}

    def note_snapshot(self, event: str, context: str = "",
                      reason: str = "") -> None:
        """Count one snapshot-plane event (write / write_error /
        restore / restore_refused / restore_error); failures retain
        context + reason for the fleet doctor."""
        with self._mu:
            self.snapshot_counts[event] = \
                self.snapshot_counts.get(event, 0) + 1
            if event in ("restore_refused", "restore_error"):
                self.last_restore_failure = {
                    "context": context, "reason": reason,
                    "ts": time.time()}

    def _invalidate_locked(self, key: str, reason: str) -> None:
        session = self._sessions.pop(key, None)
        if session is None:
            return
        session.close()
        self.invalidations[reason] = \
            self.invalidations.get(reason, 0) + 1
        metrics.counter_add(SESSION_INVALIDATIONS, reason=reason)
        ledger.record("session", session.context_dir, "invalidated",
                      reason=reason, builds=session.builds,
                      resident_bytes=session.resident_bytes())
        log.info("build session invalidated: %s (%s)",
                 session.context_dir, reason)

    def _publish_bytes_locked(self) -> None:
        total = sum(s.resident_bytes()
                    for s in self._sessions.values())
        metrics.global_registry().gauge_set(SESSION_RESIDENT_BYTES,
                                            total)

    def acquire(self, context_dir: str, identity: str,
                restore_spec: "tuple[str, str] | None" = None,
                ) -> tuple["BuildSession | None", str]:
        """Lease the context's session for one build. Returns
        ``(session, verdict)`` where verdict is one of ``hit`` (a live
        session was reused), ``restored`` (no resident session, but a
        chunk-addressed snapshot passed every invalidation check and
        was rebuilt), ``miss`` (a new session was created), or
        ``busy`` (another build holds it — caller proceeds without
        residency). ``restore_spec`` is ``(storage_dir,
        portable_identity)``; without it the snapshot plane is never
        consulted."""
        context_dir = os.path.abspath(context_dir)
        key = pathutils.real_path(context_dir)
        now = time.monotonic()
        with self._mu:
            session = self._sessions.get(key)
            if session is not None:
                if session.busy:
                    return None, "busy"
                if session.identity != identity:
                    self._invalidate_locked(key, "flag_identity")
                    session = None
                elif session.isa != _isa_identity():
                    self._invalidate_locked(key, "isa_change")
                    session = None
                elif now - session.last_used_mono > session_ttl():
                    self._invalidate_locked(key, "ttl")
                    session = None
            if session is not None:
                if restore_spec is not None:
                    session.portable_identity = restore_spec[1]
                session.busy = True
                self._publish_bytes_locked()
                return session, "hit"
        # Cold miss: consult the snapshot plane OUTSIDE the lock (the
        # shard fetch may ride the fleet peer wire — a slow peer must
        # not stall every other context's acquire).
        restored = None
        if restore_spec is not None and snapshot_policy() != "0":
            restored = self._try_restore(context_dir, identity,
                                         restore_spec)
        with self._mu:
            resident = self._sessions.get(key)
            if resident is not None:
                # A concurrent acquire of the same context won the
                # race while we restored; the resident session is the
                # single writer — ours is discarded.
                if restored is not None:
                    restored.close()
                if resident.busy:
                    return None, "busy"
                session, verdict = resident, "hit"
            else:
                session = restored if restored is not None \
                    else BuildSession(context_dir, identity)
                verdict = "restored" if restored is not None \
                    else "miss"
                if restore_spec is not None:
                    session.portable_identity = restore_spec[1]
                self._sessions[key] = session
                # Count-based LRU: evict the stalest idle session. A
                # restore that pushed the count over budget labels its
                # victims distinctly (lru_restore) so doctor can tell
                # hand-off pressure from plain churn.
                reason = ("lru_restore" if verdict == "restored"
                          else "lru")
                while len(self._sessions) > max(1, max_sessions()):
                    victims = sorted(
                        ((s.last_used_mono, k)
                         for k, s in self._sessions.items()
                         if k != key and not s.busy))
                    if not victims:
                        break
                    self._invalidate_locked(victims[0][1], reason)
            session.busy = True
            self._publish_bytes_locked()
        return session, verdict

    def _try_restore(self, context_dir: str, identity: str,
                     restore_spec: tuple) -> "BuildSession | None":
        """Attempt a snapshot restore outside the manager lock (the
        chunk fetch may ride the peer wire). Counts every outcome;
        ``absent`` (no recipe) is a plain cold miss, not a failure."""
        storage_dir, portable = restore_spec
        from makisu_tpu.worker import snapshots as snapshots_mod
        try:
            session, reason = snapshots_mod.try_restore(
                context_dir, identity, storage_dir, portable)
        except Exception as exc:  # noqa: BLE001 - advisory plane
            log.warning("session snapshot restore errored for %s: %s",
                        context_dir, exc)
            session, reason = None, "error"
        if session is not None:
            self.note_snapshot("restore", context=context_dir)
            metrics.counter_add(metrics.SESSION_SNAPSHOT_RESTORES,
                                result="ok")
            ledger.record("session", context_dir, "restored",
                          reason="snapshot",
                          resident_bytes=session.resident_bytes())
            log.info("build session restored from snapshot: %s "
                     "(exact=%s layers=%d)", context_dir,
                     session.exact, len(session.layer_replay))
            return session
        if reason:
            event = ("restore_error" if reason == "error"
                     else "restore_refused")
            self.note_snapshot(event, context=context_dir,
                               reason=reason)
            metrics.counter_add(
                metrics.SESSION_SNAPSHOT_RESTORES,
                result="refused" if event == "restore_refused"
                else "error", reason=reason)
            ledger.record("session", context_dir, "restore_refused",
                          reason=reason)
            log.info("session snapshot restore refused for %s (%s)",
                     context_dir, reason)
        return None

    def release(self, session: BuildSession) -> None:
        key = pathutils.real_path(session.context_dir)
        budget = max_resident_bytes()
        with self._mu:
            session.busy = False
            # Byte-budget evictions caused by a freshly-restored
            # session's resident bytes label lru_restore: the hand-off
            # over-budgeted the worker, which is a sizing signal, not
            # ordinary churn.
            reason = "lru_restore" if session._restore_fresh else "lru"
            session._restore_fresh = False
            # Byte budget: first shrink the releasing session's layer
            # memo, then evict whole idle sessions oldest-first.
            total = sum(s.resident_bytes()
                        for s in self._sessions.values())
            if total > budget:
                session.evict_layers(
                    max(0, budget - (total - session.resident_bytes())))
            while (sum(s.resident_bytes()
                       for s in self._sessions.values()) > budget
                   and len(self._sessions) > 1):
                victims = sorted(
                    ((s.last_used_mono, k)
                     for k, s in self._sessions.items()
                     if k != key and not s.busy))
                if not victims:
                    break
                self._invalidate_locked(victims[0][1], reason)
            self._publish_bytes_locked()

    def peek(self, context_dir: str) -> "BuildSession | None":
        """The context's live session, if any — no lease, no
        invalidation checks (the watch loop polls change state through
        it between builds)."""
        key = pathutils.real_path(os.path.abspath(context_dir))
        with self._mu:
            return self._sessions.get(key)

    def storage_dir_for(self, context_dir: str) -> str:
        """The storage dir the named context's resident session is
        bound to ("" when no resident session, or none has built yet)
        — the snapshot endpoints use it to pick the recipe's home
        among a multi-storage worker's dirs."""
        key = pathutils.real_path(os.path.abspath(context_dir))
        with self._mu:
            session = self._sessions.get(key)
            return session.storage_dir or "" if session else ""

    def invalidate(self, context_dir: str = "") -> int:
        """Explicit invalidation (the worker's POST endpoint). Empty
        context drops every non-busy session; returns the count."""
        dropped = 0
        with self._mu:
            if context_dir:
                keys = [pathutils.real_path(os.path.abspath(context_dir))]
            else:
                keys = list(self._sessions)
            for key in keys:
                session = self._sessions.get(key)
                if session is None or session.busy:
                    continue
                self._invalidate_locked(key, "explicit")
                dropped += 1
            self._publish_bytes_locked()
        return dropped

    def snapshot_all(self, context_dir: str = "",
                     force: bool = True) -> int:
        """Checkpoint every idle resident session (or one context) to
        the snapshot plane NOW — the worker's POST /sessions/snapshot
        and the fleet's drain hand-off. Writes run outside the lock;
        returns the number of sessions checkpointed."""
        want = (pathutils.real_path(os.path.abspath(context_dir))
                if context_dir else "")
        with self._mu:
            candidates = [s for k, s in self._sessions.items()
                          if not s.busy and (not want or k == want)]
        done = 0
        for session in candidates:
            if session.checkpoint(force=force) is not None:
                done += 1
        return done

    def stats(self) -> dict:
        """The ``/healthz`` sessions section + ``GET /sessions``."""
        with self._mu:
            sessions = [s.stats() for s in self._sessions.values()]
            # Copied under the lock: a concurrent first-of-its-kind
            # invalidation reason would otherwise mutate the dict mid-
            # iteration and 500 a health probe.
            invalidations = dict(self.invalidations)
            snapshot_counts = dict(self.snapshot_counts)
            last_failure = dict(self.last_restore_failure)
        sessions.sort(key=lambda s: s["context"])
        return {
            "count": len(sessions),
            "resident_bytes": sum(s["resident_bytes"]
                                  for s in sessions),
            "hits": sum(s["hits"] for s in sessions),
            "invalidations": dict(sorted(invalidations.items())),
            "max_sessions": max_sessions(),
            "max_resident_bytes": max_resident_bytes(),
            "ttl_seconds": session_ttl(),
            "snapshot": {
                **{k: snapshot_counts.get(k, 0)
                   for k in ("write", "write_error", "restore",
                             "restore_refused", "restore_error")},
                "last_restore_failure": last_failure,
            },
            "sessions": sessions,
        }

    def reset(self) -> None:
        """Drop everything (tests)."""
        with self._mu:
            for session in self._sessions.values():
                session.close()
            self._sessions.clear()
            self.invalidations.clear()
            self.snapshot_counts.clear()
            self.last_restore_failure = {}
            self._publish_bytes_locked()


_manager = SessionManager()

# Context-bound manager override: a WorkerServer binds ITS OWN
# SessionManager around every build it runs, so multiple in-process
# workers (the fleet loadgen topology, and any test standing up a
# 3-worker fleet in one interpreter) model real machines — each
# worker's resident sessions, /sessions rows, and affinity signal are
# its own, exactly as they would be across separate hosts. Standalone
# CLI builds and --watch keep the process-global manager.
_bound_manager: "contextvars.ContextVar[SessionManager | None]" = \
    contextvars.ContextVar("makisu_session_manager", default=None)


def bind_manager(mgr: SessionManager):
    """Bind ``mgr`` as the current context's session manager (threads
    the build spawns inherit it via ``contextvars.copy_context``).
    Returns a reset token."""
    return _bound_manager.set(mgr)


def reset_manager(token) -> None:
    _bound_manager.reset(token)


def manager() -> SessionManager:
    return _bound_manager.get() or _manager
