"""Filesystem walking, skip rules, and in-root symlink resolution.

Reference capability: lib/snapshot/utils.go (shouldSkip, walk,
removeAllChildren, evalSymlinks/walkLinks, CreateTarFromDirectory).

Also home of the portable dirty-set primitives (``snapshot_tree`` /
``snapshot_delta``): a stat-signature snapshot of a context tree and
the walk-based delta between two snapshots — the mtime-walk fallback
the resident build session (worker/session.py) uses when inotify is
unavailable. One scandir pass, no content reads.
"""

from __future__ import annotations

import dataclasses
import os
import stat as statmod
import tarfile
import time

from makisu_tpu import native, tario
from makisu_tpu.utils import metrics, mountinfo, pathutils, sysutils

WHITEOUT_PREFIX = ".wh."
WHITEOUT_META_PREFIX = ".wh..wh."


def should_skip(path: str, st: os.stat_result | None,
                blacklist: list[str]) -> bool:
    """Paths that never participate in snapshots: AUFS whiteout metadata,
    blacklisted trees, special files, and mount points."""
    if os.path.basename(path).startswith(WHITEOUT_META_PREFIX):
        return True
    if pathutils.is_descendant_of_any(path, blacklist):
        return True
    if st is not None and sysutils.is_special_file(st):
        return True
    return mountinfo.is_mountpoint(path)


def _read_dir(path: str, want_stat: bool) -> list[tuple]:
    """``path``'s children in the directory's own order, from disk:
    ``(name, lstat)`` with ``want_stat`` (a child gone before its
    ``lstat`` is left out, as if the listing had run a moment later),
    else ``(name, is a directory itself and no link to one)`` from the
    type bits alone.

    Where ``libdirscan.so`` was built the directory is one foreign call
    with the interpreter lock free from the ``open`` to the last
    ``lstat``: one hand-back of the lock a directory. The ``scandir``
    body hands it back at every ``readdir`` and every ``lstat``, two an
    entry, each a turn in the queue where several builds share the
    interpreter; it is the route where no library was built and the
    reference the tests hold the other to."""
    reader = native.dir_reader()
    out = reader.read(path, want_stat) if reader is not None else None
    route = "native" if out is not None else "python"
    if out is None:
        out = []
        with os.scandir(path) as it:
            for entry in it:
                if not want_stat:
                    out.append((entry.name,
                                entry.is_dir(follow_symlinks=False)))
                    continue
                try:
                    out.append((entry.name,
                                entry.stat(follow_symlinks=False)))
                except FileNotFoundError:
                    continue
    metrics.counter_add(metrics.DIR_READS_TOTAL, route=route,
                        stat="1" if want_stat else "0")
    return out


def _list_dir(path: str) -> list[tuple[str, os.stat_result]]:
    """``path``'s children as ``(name, lstat)``, sorted by name."""
    out = _read_dir(path, True)
    out.sort(key=lambda child: child[0])
    return out


def child_dirs(path: str) -> list[str]:
    """The names of ``path``'s children that are directories (type bits
    only, no ``lstat`` a file), in the directory's own order."""
    return [name for name, is_dir in _read_dir(path, False) if is_dir]


class TreeListing:
    """One build's memo of its context tree: what ``scandir`` and
    ``lstat`` said of each directory and entry the first time any pass
    of the build asked. A build's passes over its context (the
    ``copy_checksum`` of ``AddCopyStep``, the layer scan's ``walk``,
    the session checkpoint's ``snapshot_tree``) see one tree, statted
    once. The listing is lazy and has no roles: whichever pass reaches
    a directory first lists it, every later one replays it.

    It serves only paths under ``root`` (the build's ``context_dir``),
    which a build reads and does not write. ``close()`` ends that for
    the rest of the build (a ``RUN`` step may write anywhere): from
    then on ``serves`` is false and every pass lists live. It lives on
    the ``BuildContext`` and goes with it, so no build sees another's.

    A racing edit: a file edited after its stat was taken and before
    the layer scan gets a header from the memoized stat, the one its
    cache id was computed from ("a layer holds the tree's files as they
    are on disk when the build starts"); the tar writer's handling of
    a size that no longer matches is untouched, and the session's
    watcher, armed before the first stat, puts the path in the next
    build's dirty set. ``started_ns`` is the wall clock before the
    first ``lstat``: a snapshot built from replayed stats certifies
    against that moment, not against the time of the replay.

    Holds a ``stat_result`` an entry listed until the build ends.
    """

    def __init__(self, root: str) -> None:
        self.root = os.path.abspath(root)
        self._prefix = self.root.rstrip("/") + "/"
        self._dirs: dict[str, list[tuple[str, os.stat_result]]] | None = {}
        self._stats: dict[str, os.stat_result] = {}
        self.started_ns: int | None = None
        # Directories read from disk and answered from the memo since
        # the last ``flush_counts`` (added to the counter once a pass).
        self.asked = {"listed": 0, "replayed": 0}

    def _key(self, path: str) -> str | None:
        """The memo's key for ``path``; None where it is not served.
        "COPY . /app/" resolves to "<context>/." and "COPY app/" to a
        trailing slash: the same directories, one key each. A path with
        "..", which may cross a symlink, is not served."""
        if self._dirs is None:
            return None
        parts = path.split("/")
        if parts[0] or ".." in parts:
            return None
        path = "/" + "/".join(p for p in parts if p and p != ".")
        if path == self.root or path.startswith(self._prefix):
            return path
        return None

    def serves(self, path: str) -> bool:
        return self._key(path) is not None

    def _start(self) -> None:
        if self.started_ns is None:
            self.started_ns = time.time_ns()

    def lstat(self, path: str) -> os.stat_result:
        """``os.lstat(path)``, memoized where ``path`` is served: for
        the top of a source, which no listed directory holds."""
        key = self._key(path)
        if key is None:
            return os.lstat(path)
        st = self._stats.get(key)
        if st is None:
            self._start()
            st = self._stats[key] = os.lstat(path)
        return st

    def children(self, path: str
                 ) -> list[tuple[str, str, os.stat_result]]:
        """The children of directory ``path`` as ``(name, path,
        lstat)``, sorted by name (the order of ``sorted(os.listdir)``);
        the child paths are joined onto ``path`` as it was given."""
        key = self._key(path)
        if key is None:
            listed = _list_dir(path)
        elif key in self._dirs:
            listed = self._dirs[key]
            self.asked["replayed"] += 1
        else:
            self._start()
            listed = self._dirs[key] = _list_dir(path)
            self.asked["listed"] += 1
        return [(name, os.path.join(path, name), st)
                for name, st in listed]

    def close(self) -> None:
        """Drop the memo and serve nothing more: the build is about to
        run something that may write the context."""
        self._dirs = None
        self._stats = {}
        self.started_ns = None

    def flush_counts(self) -> None:
        """Add this pass's directories to
        ``makisu_tree_listing_dirs_total`` (once a pass, never an
        entry) and start the next pass's count."""
        for result, n in self.asked.items():
            if n:
                metrics.counter_add(metrics.TREE_LISTING_DIRS_TOTAL, n,
                                    result=result)
                self.asked[result] = 0


# What a walk with no listing goes through: closed, it serves nothing,
# keeps nothing and counts nothing, and every call is the live one.
_NO_LISTING = TreeListing("/")
_NO_LISTING.close()


def walk(src_root: str, blacklist: list[str] | None, fn,
         listing: TreeListing | None = None) -> None:
    """Depth-first lexical walk calling ``fn(path, stat)``; prunes skipped
    directories. Includes ``src_root`` itself (like filepath.Walk).

    One ``scandir`` a directory and one ``lstat`` an entry. With a
    ``listing`` (a build's, of its context tree) what lies under the
    listing's root is asked of it instead: the walk fills what no
    earlier pass of the build listed and replays the rest, with no
    file-system call; anything else is listed live all the same. Visit
    order and what ``fn`` receives are the same either way, and
    ``should_skip`` runs on every entry with the caller's own
    blacklist."""
    blacklist = blacklist or []
    if listing is None:
        listing = _NO_LISTING
    st = listing.lstat(src_root)
    if should_skip(src_root, st, blacklist):
        return
    fn(src_root, st)
    if not statmod.S_ISDIR(st.st_mode):
        return
    # Explicit iterator stack (not recursion): trees deeper than
    # Python's ~1000-frame limit must not crash the layer scan. Visit
    # order is identical to the recursive form — each entry fires in
    # sorted order, descending into a directory before its siblings.
    stack = [iter(listing.children(src_root))]
    while stack:
        child = next(stack[-1], None)
        if child is None:
            stack.pop()
            continue
        _, path, st = child
        if should_skip(path, st, blacklist):
            continue
        fn(path, st)
        if statmod.S_ISDIR(st.st_mode):
            stack.append(iter(listing.children(path)))
    listing.flush_counts()


# -- dirty-set primitives ---------------------------------------------------

# A path's stat signature for change detection. ctime_ns is the
# linchpin: size+mtime can be restored by tooling (utime), but a
# content write always bumps ctime — the same discipline as the
# stat-keyed content-ID cache (utils/statcache.py).
def stat_signature(st: os.stat_result) -> tuple:
    return (st.st_mode, st.st_size, st.st_mtime_ns, st.st_ctime_ns,
            st.st_ino)


@dataclasses.dataclass
class TreeSnapshot:
    """Stat signatures of every path under a root at capture time.
    ``fresh`` holds paths whose timestamps were within the racy window
    of the capture — a same-tick edit after the capture would alias
    their signature, so a delta against this snapshot re-marks them
    dirty once (bounded re-hash, never a stale identity)."""

    root: str
    captured_ns: int
    sigs: dict[str, tuple]
    fresh: set[str]
    # Resident-byte estimate, computed once at capture: callers
    # (session accounting, /healthz) poll it far too often for an
    # O(paths) re-sum per call.
    est_bytes: int = 0

    def approx_bytes(self) -> int:
        return self.est_bytes


@dataclasses.dataclass
class TreeDelta:
    """Paths that moved between two snapshots of one root. ``dirty``
    is the union view consumers key skip-decisions on: changed ∪ added
    ∪ removed ∪ the previous snapshot's racy-fresh survivors."""

    changed: set[str]
    added: set[str]
    removed: set[str]
    fresh: set[str]

    @property
    def dirty(self) -> set[str]:
        return self.changed | self.added | self.removed | self.fresh

    @property
    def real_dirty(self) -> set[str]:
        """Signature-confirmed changes only (no racy re-checks): what
        a watch loop triggers rebuilds on — fresh-only dirt would
        rebuild once per racy window with no actual edit."""
        return self.changed | self.added | self.removed


def _racy_window_ns() -> int:
    from makisu_tpu.utils import statcache
    return statcache.racy_window_ns()


def snapshot_tree(root: str, blacklist: list[str] | None = None,
                  listing: TreeListing | None = None) -> TreeSnapshot:
    """One scandir+lstat pass capturing every path's stat signature
    (the root itself excluded — its mtime churns with child churn and
    carries no content identity of its own).

    With a build's ``listing`` (the session's checkpoint hands in the
    one its build filled) the pass replays what the build's earlier
    passes statted and lists only the rest. The snapshot then certifies
    against the moment the first of those stats was taken:
    ``captured_ns`` is the listing's ``started_ns``, so a same-tick
    edit after a stat is still ``fresh`` and re-marked once."""
    captured_ns = time.time_ns()
    if (listing is not None and listing.started_ns is not None
            and listing.serves(root)):
        captured_ns = listing.started_ns
    window = _racy_window_ns()
    sigs: dict[str, tuple] = {}
    fresh: set[str] = set()

    def visit(path: str, st: os.stat_result) -> None:
        if path == root:
            return
        sigs[path] = stat_signature(st)
        if captured_ns - max(st.st_mtime_ns, st.st_ctime_ns) < window:
            fresh.add(path)

    walk(root, blacklist, visit, listing)
    # Rough accounting: path string + signature tuple per entry.
    return TreeSnapshot(root, captured_ns, sigs, fresh,
                        sum(len(p) + 120 for p in sigs))


def snapshot_delta(prev: TreeSnapshot,
                   blacklist: list[str] | None = None
                   ) -> tuple[TreeSnapshot, TreeDelta]:
    """Re-walk ``prev.root`` and compute what moved since ``prev``.
    Returns the fresh snapshot (the next delta's baseline) and the
    delta. Cost is one stat walk — no content reads, no hashing."""
    cur = snapshot_tree(prev.root, blacklist)
    changed = {p for p, sig in cur.sigs.items()
               if p in prev.sigs and prev.sigs[p] != sig}
    added = set(cur.sigs) - set(prev.sigs)
    removed = set(prev.sigs) - set(cur.sigs)
    # Racy survivors: paths the previous capture couldn't certify
    # (same-tick timestamps). If their signature moved they're already
    # in `changed`; if not, they still get one dirty round.
    fresh = {p for p in prev.fresh if p in cur.sigs} - changed
    return cur, TreeDelta(changed, added, removed, fresh)


def remove_all_children(src_root: str, blacklist: list[str]) -> None:
    """Delete everything under src_root except skipped paths, keeping any
    directory that still holds a surviving (blacklisted/mounted) child.

    Iterative (deep trees must not hit the recursion limit): collect
    candidates depth-first, then delete deepest-first — a directory with
    a surviving child simply fails its rmdir and is kept, which is
    exactly the recursive semantics."""
    stack = [os.path.join(src_root, name) for name in os.listdir(src_root)]
    order: list[tuple[str, bool]] = []
    while stack:
        path = stack.pop()
        try:
            st = os.lstat(path)
        except OSError:
            continue  # already gone
        if should_skip(path, st, blacklist):
            continue  # kept; its ancestors fail rmdir and survive too
        # The lstat says "a directory and not a link": nothing asks again.
        is_dir = statmod.S_ISDIR(st.st_mode)
        order.append((path, is_dir))
        if is_dir:
            # An unreadable dir (EACCES) must fail the cleanup loudly —
            # silently keeping its contents would leak stage-1 files
            # into stage-2 layers. A dir deleted since lstat is a benign
            # race (the delete loop below tolerates it too).
            try:
                names = os.listdir(path)
            except (FileNotFoundError, NotADirectoryError):
                continue  # deleted/replaced since lstat: benign race
            stack.extend(os.path.join(path, name) for name in names)
    for path, is_dir in reversed(order):
        try:
            if is_dir:
                os.rmdir(path)
            else:
                os.remove(path)
        except OSError:
            pass  # nonempty dir (surviving child) or racing delete


def eval_symlinks(path: str, root: str) -> str:
    """Resolve symlinks of a root-relative path *within* root, returning the
    absolute logical path. Links may not escape the root; loops error."""
    if not path:
        return path
    resolved: list[str] = []
    walked = 0
    pending = pathutils.split_path(path)
    while pending:
        part = pending.pop(0)
        cur_logical = "/" + "/".join(resolved + [part])
        cur_disk = pathutils.join_root(root, cur_logical)
        try:
            st = os.lstat(cur_disk)
        except FileNotFoundError:
            resolved.append(part)
            continue
        if not os.path.islink(cur_disk):
            resolved.append(part)
            continue
        walked += 1
        if walked > 255:
            raise OSError(f"eval symlinks: too many links at {path}")
        target = os.readlink(cur_disk)
        if os.path.isabs(target):
            if target.startswith(root.rstrip("/") + "/") or target == root:
                target = pathutils.trim_root(target, root)
            resolved = []
        pending = pathutils.split_path(target) + pending
    return "/" + "/".join(resolved)


def create_tar_from_directory(target: str, src_dir: str) -> None:
    """Gzip-tar a directory tree with hardlink dedup by inode
    (reference: CreateTarFromDirectory utils.go:156)."""
    inodes: dict[int, str] = {}
    with open(target, "wb") as f:
        with tario.gzip_writer(f) as gz:
            with tarfile.open(fileobj=gz, mode="w|") as tw:
                def one(path: str, st: os.stat_result) -> None:
                    if path == src_dir:
                        return
                    name = pathutils.rel_path(
                        pathutils.trim_root(path, src_dir))
                    hdr = tarinfo_from_stat(path, name, src_dir, st)
                    if hdr.isreg():
                        if st.st_ino in inodes:
                            hdr.type = tarfile.LNKTYPE
                            hdr.linkname = inodes[st.st_ino]
                            hdr.size = 0
                        else:
                            inodes[st.st_ino] = hdr.name
                    tario.write_entry(tw, path, hdr)

                walk(src_dir, None, one)


def tarinfo_from_stat(src: str, name: str, root: str,
                      st: os.stat_result | None = None) -> tarfile.TarInfo:
    """Build a TarInfo from an on-disk path, from the ``lstat`` the
    caller's walk already took of it where it hands one in.

    Directory names get docker's trailing slash; absolute symlink targets
    are rebased to be root-relative (reference: memLayer.createHeader,
    mem_layer.go:~110-140).
    """
    if st is None:
        st = os.lstat(src)
    hdr = tarfile.TarInfo(name)
    hdr.mode = st.st_mode & 0o7777
    hdr.uid = st.st_uid
    hdr.gid = st.st_gid
    hdr.mtime = int(st.st_mtime)
    hdr.uname = ""
    hdr.gname = ""
    if statmod.S_ISDIR(st.st_mode):
        # (tarfile adds docker's trailing slash to dir names at write time)
        hdr.type = tarfile.DIRTYPE
    elif statmod.S_ISLNK(st.st_mode):
        hdr.type = tarfile.SYMTYPE
        target = os.readlink(src)
        if os.path.isabs(target):
            target = pathutils.trim_root(target, root)
        hdr.linkname = target
    else:
        hdr.type = tarfile.REGTYPE
        hdr.size = st.st_size
    return hdr
