"""MemFS: the in-memory merged filesystem view driving layer generation.

The tree holds the header of every path as of the layers applied so far.
Committing a step diffs reality against the tree:

- ``add_layer_by_scan`` walks the disk (after RUN steps) and emits entries
  whose headers differ from the tree, plus whiteouts for tree children
  that vanished from disk.
- ``add_layer_by_copy_ops`` computes the layer purely from ADD/COPY
  operations without scanning.
- ``update_from_tar`` merges a pulled layer into the tree (optionally
  materializing it on disk), honoring whiteouts.

A cached layer reaches the tree only when the tree is read. The build
node hands its application over (``defer``); every method that reads or
writes ``tree`` first applies what is pending, in order (``flush``). A
stage whose later steps are all cache hits never reads the tree, drops
its pending applications when it ends (``drop_pending``), and so never
opens those layers' blobs: the image's manifest and config come from
the digests the cache returned. Such a build reads no byte of those
blobs, so their integrity at rest is the storage plane's work (the
scrub, ``doctor --storage``), as for every blob a build does not touch.

The diff compares mtimes in whole seconds, so both commits hold one
invariant: a write made after a commit returns is stamped in a later
second than every mtime that commit's scan visited. The reference
sleeps a second before each scan (mem_fs.go sync, :294-311); here the
scan remembers the newest mtime it visits and the commit waits, after
its tar is written, only while the clock is still inside that second
(``MemFS._wait_out_mtime``).

Reference capability: lib/snapshot/mem_fs.go (NewMemFS:69,
UpdateFromTarReader:165, AddLayerByScan:260, AddLayerByCopyOps:276,
Checkpoint:91, CompareFS:720); the implementation is a fresh design over
tarfile.TarInfo headers.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import stat as statmod
import tarfile
import time
from collections.abc import Callable
from glob import glob

from makisu_tpu import tario
from makisu_tpu.snapshot.copy_op import CopyOperation
from makisu_tpu.snapshot.layer import ContentEntry, Layer, WhiteoutEntry
from makisu_tpu.snapshot.walk import (
    WHITEOUT_META_PREFIX,
    WHITEOUT_PREFIX,
    TreeListing,
    eval_symlinks,
    remove_all_children,
    tarinfo_from_stat,
    walk,
)
from makisu_tpu.utils import fileio, metrics, mountinfo, pathutils
from makisu_tpu.utils import logging as log
from makisu_tpu.utils.fileio import Owner

_MAX_SYMLINK_DEPTH = 64

# A file system stamps mtimes from its own clock, the wait's deadline is
# read from time.time(): the seconds a stamp may trail that read. Linux
# stamps from the coarse clock, up to a tick behind (10 ms at HZ=100);
# 9p under gVisor trailed by at most 0.5 ms over 1,000 files (PERF.md
# §6, PR 27). Twice the longest tick.
_CLOCK_MARGIN = 0.02

# Bytes of a member read from its tar and written under the root at a
# time.
_UNTAR_BLOCK = 1 << 20


class Node:
    """One path in the merged view: header + disk source + children."""

    __slots__ = ("src", "dst", "hdr", "children")

    def __init__(self, src: str, dst: str, hdr: tarfile.TarInfo) -> None:
        self.src = src
        self.dst = dst
        self.hdr = hdr
        self.children: dict[str, Node] = {}

    def is_on_disk(self) -> bool:
        return os.path.lexists(self.src)


@dataclasses.dataclass
class FSDiff:
    """Result of comparing two MemFS trees (diff command)."""

    missing_in_first: list[str]
    missing_in_second: list[str]
    different: list[tuple[str, tarfile.TarInfo, tarfile.TarInfo]]

    @property
    def empty(self) -> bool:
        return not (self.missing_in_first or self.missing_in_second
                    or self.different)


class MemFS:
    def __init__(self, root: str, blacklist: list[str] | None = None,
                 sync_wait: float = 1.0) -> None:
        os.lstat(root)  # must exist
        self.root = root
        self.blacklist = list(blacklist or [])
        self.sync_wait = sync_wait
        hdr = tarinfo_from_stat(root, "", root)
        hdr.name = ""  # "/" itself never appears in layers
        self.tree = Node(root, "/", hdr)
        self.layers: list[Layer] = []
        self._isa_logged = False  # route logged once per build (MemFS)
        # When set (a list), _apply_entry mirrors every applied entry
        # into it — the op stream replay_layer folds back verbatim.
        self._record_ops: list | None = None
        # Applied-layer chain identity: a rolling digest over the
        # layers folded into this tree, in order. A recorded op stream
        # is only valid at the exact chain position it was recorded at
        # (the ops bake in that tree state's diff outcome), so the
        # session's replay memo keys on (applied_chain, digest). Any
        # tar merge that can't name its layer taints the chain and
        # turns the memo off for this tree.
        self.applied_chain = ""
        self.chain_tainted = False
        # Cached layers not folded into the tree yet: (digest, apply),
        # oldest first. See ``defer``.
        self._pending: list[tuple[str, Callable[[], None]]] = []

    def extend_chain(self, digest_hex: str) -> None:
        import hashlib
        self.applied_chain = hashlib.sha256(
            (self.applied_chain + digest_hex).encode()).hexdigest()

    # ------------------------------------------------------------------
    # Deferred application of cached layers
    # ------------------------------------------------------------------

    def defer(self, digest_hex: str, apply: Callable[[], None]) -> None:
        """Queue one cached layer's application. ``apply`` folds the
        layer into this tree (through ``update_from_tar`` or
        ``replay_layer``) and runs at the next ``flush``, after every
        application queued before it: the tree, ``applied_chain`` and
        the replay memo's keys come out as if each had run at once."""
        self._pending.append((digest_hex, apply))

    def flush(self) -> None:
        """Apply what is pending, in order. Called at the top of every
        method that reads or writes ``tree``; with nothing pending it
        is one truth test."""
        if not self._pending:
            return
        # Taken first: each application re-enters through a method
        # that flushes.
        pending, self._pending = self._pending, []
        for _, apply in pending:
            apply()

    def drop_pending(self) -> list[str]:
        """Forget the pending applications: the stage has ended and
        nothing read the tree. Returns the digests dropped, in order."""
        pending, self._pending = self._pending, []
        return [digest_hex for digest_hex, _ in pending]

    # ------------------------------------------------------------------
    # Tree bookkeeping
    # ------------------------------------------------------------------

    def reset(self) -> None:
        self.tree.children = {}

    def remove(self) -> None:
        """Wipe the on-disk filesystem under root (between stages)."""
        remove_all_children(self.root, self.blacklist)

    def _apply_entry(self, entry: ContentEntry | WhiteoutEntry) -> None:
        """Fold a layer entry into the tree."""
        if self._record_ops is not None:
            self._record_ops.append(entry)
        if isinstance(entry, WhiteoutEntry):
            parts = pathutils.split_path(entry.deleted)
            node = self.tree
            for part in parts[:-1]:
                child = node.children.get(part)
                if child is None:
                    raise FileNotFoundError(
                        f"missing intermediate dir in {entry.deleted}")
                node = child
            if node.children.pop(parts[-1], None) is None:
                log.warning("whiteout of nonexistent path: %s", entry.deleted)
            return
        parts = pathutils.split_path(entry.dst)
        node = self.tree
        for part in parts[:-1]:
            child = node.children.get(part)
            if child is None:
                raise FileNotFoundError(
                    f"missing intermediate directory {part} in {entry.dst}")
            node = child
        new = Node(entry.src, entry.dst, entry.hdr)
        old = node.children.get(parts[-1]) if parts else None
        if old is not None and entry.hdr.isdir():
            new.children = old.children  # replacing a dir keeps its children
        if parts:
            node.children[parts[-1]] = new

    def _lookup(self, dst: str) -> Node | None:
        node = self.tree
        for part in pathutils.split_path(dst):
            node = node.children.get(part)
            if node is None:
                return None
        return node

    def _is_updated(self, dst: str,
                    hdr: tarfile.TarInfo) -> tuple[bool, Node | None]:
        node = self._lookup(dst)
        if node is None:
            return True, None
        return not tario.is_similar_header(node.hdr, hdr), node

    # ------------------------------------------------------------------
    # Layer creation
    # ------------------------------------------------------------------

    def _sync(self) -> None:
        """Flush pending writes before a scan."""
        with metrics.span("memfs_sync"):
            with metrics.span("memfs_sync.os_sync"):
                try:
                    os.sync()
                except (OSError, AttributeError):
                    pass

    def _wait_out_mtime(self, newest: int) -> None:
        """Return once a later write cannot share a whole-second mtime
        with anything the scan visited: when the clock has passed the
        second of ``newest``, the newest mtime visited, added to the
        layer or not. Never sleeps longer than ``sync_wait``, the
        reference's sleep before each scan (mem_fs.go sync, :294-311)
        and all an mtime in the future gets. Called after the tar
        write, so that an mtime of a moment ago is old by now."""
        wait = max(0.0, min(newest + 1 + _CLOCK_MARGIN - time.time(),
                            self.sync_wait))
        with metrics.span("memfs_sync.mtime_wait", wait_s=f"{wait:.3f}"):
            if wait > 0:
                time.sleep(wait)
        metrics.counter_add(metrics.MTIME_WAIT_TOTAL,
                            result="slept" if wait > 0 else "clear")

    def add_layer_by_scan(self, tw: tarfile.TarFile) -> Layer:
        self.flush()
        self._sync()
        with metrics.span("layer_scan", kind="scan") as sp:
            layer, newest, visited = self._create_layer_by_scan()
            sp.set(entries=len(layer), visited=visited)
        whiteouts = sum(isinstance(e, WhiteoutEntry)
                        for e in layer.entries.values())
        for result, n in (("visited", visited),
                          ("added", len(layer) - whiteouts),
                          ("whiteout", whiteouts)):
            metrics.counter_add(metrics.SCAN_ENTRIES_TOTAL, n, result=result)
        self._commit_layer(layer, tw)
        self._wait_out_mtime(newest)
        log.info("created layer by scan: %d entries", len(layer))
        return layer

    def add_layer_by_copy_ops(self, ops: list[CopyOperation],
                              tw: tarfile.TarFile,
                              listing: TreeListing | None = None) -> Layer:
        """``listing``: the build's listing of its context tree
        (``BuildContext.listing``); an external op's source is walked
        through it."""
        self.flush()
        self._sync()
        with metrics.span("layer_scan", kind="copy_ops") as sp:
            layer = Layer()
            newest = max((self._add_copy_to_layer(layer, op, listing)
                          for op in ops), default=0)
            sp.set(entries=len(layer))
        self._commit_layer(layer, tw)
        self._wait_out_mtime(newest)
        log.info("created copy layer: %d entries", len(layer))
        return layer

    def _commit_layer(self, layer: Layer, tw: tarfile.TarFile) -> None:
        # The single funnel for scan and copy-op commits: wall time
        # here is the tar_write stage of the commit pipeline (the
        # ordered producer the read-ahead / chunk-SHA / compress
        # stages overlap) — `makisu-tpu report` ranks the stages to
        # name the bottleneck.
        if not self._isa_logged:
            # One MemFS per build: the first layer commit names the
            # resolved SIMD route in the build log (dispatch is chosen
            # once per process in native.py). Throughput knob only —
            # never part of cache identity. The flag burns only once a
            # route exists, so a commit that lands before the native
            # library loads doesn't swallow the line for the build.
            from makisu_tpu import native
            route = native.isa_route_if_resolved()
            if route is not None:
                self._isa_logged = True
                log.info("layer-commit native ISA route: %s", route)
        try:
            with metrics.span("tar_write", entries=len(layer)) as sp:
                layer.commit(tw)
                sp.set(bytes=tw.offset)
        finally:
            # The span's pair of clock reads is the stage counter's.
            metrics.stage_busy_add("tar_write", sp.duration)
        # Once a layer, one add a kind present (never an add an entry:
        # a 50k-entry layer must not pay 50k counter locks).
        for kind, n in layer.kind_counts().items():
            metrics.counter_add(metrics.LAYER_ENTRIES_TOTAL, n, kind=kind)
        # A commit folded entries into the tree without a chain key
        # (its digest exists only after the fact): any later cached
        # application on this tree must bypass the replay memo.
        self.chain_tainted = True
        self.layers.append(layer)

    def _create_layer_by_scan(self) -> tuple[Layer, int, int]:
        """The layer, the newest mtime of any entry visited, and how
        many were."""
        layer = Layer()
        newest = visited = 0

        def visit(path: str, st: os.stat_result) -> None:
            nonlocal newest, visited
            visited += 1
            dst = pathutils.trim_root(path, self.root)
            hdr = tarinfo_from_stat(path, pathutils.rel_path(dst), self.root,
                                    st)
            newest = max(newest, hdr.mtime)
            self._maybe_add(layer, path, dst, hdr, create_whiteouts=True)

        walk(self.root, self.blacklist, visit)
        return layer, newest, visited

    def _maybe_add(self, layer: Layer, src: str, dst: str,
                   hdr: tarfile.TarInfo, create_whiteouts: bool) -> None:
        """Add ``dst`` to the layer if its header differs from the tree;
        optionally emit whiteouts for tree children gone from disk."""
        updated, node = self._is_updated(dst, hdr)
        if updated and dst != "/":
            self._add_ancestors(layer, dst, inclusive=False)
            self._apply_entry(layer.add_header(src, dst, hdr))
        if create_whiteouts and hdr.isdir() and node is not None:
            for child in list(node.children.values()):
                # Existence is judged at the child's logical path under
                # the build root — not entry.src, which for copy-op
                # entries points at the (still-existing) context file.
                disk = pathutils.join_root(self.root, child.dst)
                if not os.path.lexists(disk):
                    self._add_ancestors(layer, child.dst, inclusive=False)
                    entry = layer.add_whiteout(child.dst)
                    self._apply_entry(entry)

    def _add_ancestors(self, layer: Layer, dst: str, inclusive: bool,
                       uid: int = 0, gid: int = 0, depth: int = 0) -> str:
        """Record every ancestor of ``dst`` into the layer (docker tars
        carry parent dirs of each entry), resolving in-tree symlinks, and
        synthesize missing intermediate directories. Returns the resolved
        destination path."""
        if depth >= _MAX_SYMLINK_DEPTH:
            raise OSError(f"symlink loop resolving {dst}")
        parts = pathutils.split_path(dst)
        end = len(parts) if inclusive else len(parts) - 1
        node = self.tree
        last_dir = self.tree
        i = 0
        while i < end:
            child = node.children.get(parts[i])
            if child is None:
                break
            # Skip the re-add when this exact ancestor entry is already
            # in the layer (every descendant repeats its whole chain;
            # on a cold scan that is O(depth) redundant header work).
            existing = layer.entries.get(child.dst)
            if not (isinstance(existing, ContentEntry)
                    and existing.hdr is child.hdr):
                self._apply_entry(
                    layer.add_header(child.src, child.dst, child.hdr))
            if child.hdr.isdir():
                node = child
                last_dir = child
                i += 1
            elif child.hdr.issym():
                target = child.hdr.linkname
                if not os.path.isabs(target):
                    target = os.path.join(
                        os.path.dirname(child.dst), target)
                target = pathutils.abs_path(
                    os.path.join(target, *parts[i + 1:]))
                return self._add_ancestors(
                    layer, target, inclusive, uid, gid, depth + 1)
            else:
                break  # plain file mid-path; nothing to descend into
        for j in range(i, end):
            cur = "/" + "/".join(parts[:j + 1])
            hdr = tarfile.TarInfo(pathutils.rel_path(cur))
            hdr.type = tarfile.DIRTYPE
            hdr.mode = last_dir.hdr.mode
            # Epoch mtime, not the wall clock: a synthesized ancestor
            # (e.g. /app for COPY . /app/) exists in no source tree, so
            # any live timestamp would make two builds of identical
            # inputs differ whenever they straddle a second boundary —
            # silently breaking the byte-reproducibility COPY layers
            # promise (and cache/dedup identity with it). Same policy
            # as heredoc-generated files (steps/add_copy.py).
            hdr.mtime = 0
            hdr.uid = uid
            hdr.gid = gid
            self._apply_entry(layer.add_header("", cur, hdr))
        return dst

    def _add_copy_to_layer(self, layer: Layer, op: CopyOperation,
                           listing: TreeListing | None = None) -> int:
        """Returns the newest mtime of any source entry visited."""
        newest = 0
        create_dst = True
        if len(op.srcs) == 1:
            only = pathutils.join_root(op.src_root, op.srcs[0])
            if not os.path.isdir(only):  # follows symlinks
                create_dst = False
        dst = op.dst
        if create_dst:
            resolved = self._add_ancestors(
                layer, pathutils.abs_path(dst), inclusive=True,
                uid=op.uid, gid=op.gid)
            dst = resolved if resolved.endswith("/") else resolved + "/"
        for rel_src in op.srcs:
            rel_src = eval_symlinks(rel_src, op.src_root)
            src = pathutils.join_root(op.src_root, rel_src)

            def visit(cur: str, st: os.stat_result,
                      src=src, dst=dst) -> None:
                nonlocal newest
                if cur == src:
                    if statmod.S_ISDIR(st.st_mode):
                        return  # dir contents copy into dst, not dir itself
                    if not dst.endswith("/"):
                        cur_dst = dst
                    else:
                        cur_dst = os.path.join(dst, os.path.basename(src))
                else:
                    cur_dst = os.path.join(dst, cur[len(src):].lstrip("/"))
                hdr = tarinfo_from_stat(
                    cur, pathutils.rel_path(cur_dst), self.root, st)
                if op.preserve_owner:
                    pass  # keep source owners (--archive)
                else:
                    hdr.uid = op.uid
                    hdr.gid = op.gid
                newest = max(newest, hdr.mtime)
                self._maybe_add(layer, cur, pathutils.abs_path(cur_dst), hdr,
                                create_whiteouts=False)

            # Same blacklist policy as the on-disk Copier (copy_op.py
            # _copier): external copies prune blacklisted sources —
            # incl. .dockerignore exclusions — internal (--from) copies
            # see everything in their sandbox, which the build itself
            # wrote: never through the listing.
            if op.internal:
                walk(src, None, visit)
            else:
                walk(src, op.blacklist, visit, listing)
        return newest

    # ------------------------------------------------------------------
    # Tar merging / untarring
    # ------------------------------------------------------------------

    def update_from_tar_path(self, source: str, untar: bool) -> Layer:
        with open(source, "rb") as f:
            with tario.gzip_reader(f) as gz, tario.layer_tar(gz) as tf:
                return self.update_from_tar(tf, untar)

    def update_from_tar(self, tf: tarfile.TarFile, untar: bool,
                        record: list | None = None,
                        chain_key: str | None = None) -> Layer:
        """Merge one layer tar into the tree; ``untar`` also materializes
        it on disk. Hardlinks apply in a second pass (their targets may
        appear later in the tar); parent-directory mtimes are restored
        after extraction.

        ``record`` (a list to fill) captures the exact entry stream
        this application folded into the tree — the input
        :meth:`replay_layer` accepts, so a resident build session can
        re-apply this layer without re-inflating the blob.
        ``chain_key`` names the layer (its blob digest) for the
        applied-chain identity; merges that can't name one taint the
        chain (diff/extract flows, which never consult the memo)."""
        self.flush()
        layer = Layer()
        hardlinks: list[tuple[str, tarfile.TarInfo]] = []
        parent_mtimes: dict[str, float] = {}
        unpacked = 0
        members = {"created": 0, "probed": 0}
        if record is not None:
            self._record_ops = record
        try:
            for hdr in tf:
                hdr.name = pathutils.rel_path(hdr.name)
                disk_path = pathutils.join_root(self.root, hdr.name)
                if self._skip_tar_member(disk_path, hdr):
                    continue
                if untar:
                    parent = os.path.dirname(disk_path)
                    if parent not in parent_mtimes:
                        try:
                            parent_mtimes[parent] = \
                                os.lstat(parent).st_mtime
                        except FileNotFoundError:
                            # The tar names no such directory: it is made
                            # with the member and has no mtime to keep.
                            pass
                if hdr.islnk():
                    hdr.linkname = pathutils.abs_path(hdr.linkname)
                    hardlinks.append((disk_path, hdr))
                    continue
                if untar:
                    written, result = self._untar_one(disk_path, hdr, tf)
                    unpacked += written
                    members[result] += 1
                self._maybe_add(layer, disk_path,
                                pathutils.abs_path(hdr.name),
                                hdr, create_whiteouts=False)
            for disk_path, hdr in hardlinks:
                if untar:
                    _, result = self._untar_one(disk_path, hdr, None)
                    members[result] += 1
                self._maybe_add(layer, disk_path,
                                pathutils.abs_path(hdr.name),
                                hdr, create_whiteouts=False)
        finally:
            self._record_ops = None
        for parent, mtime in parent_mtimes.items():
            os.utime(parent, (mtime, mtime))
        if untar:
            metrics.counter_add(metrics.ON_DISK_BYTES_TOTAL, unpacked,
                                op="untar")
            for result, n in members.items():
                metrics.counter_add(metrics.UNTAR_MEMBERS_TOTAL, n,
                                    result=result)
        if chain_key is not None:
            self.extend_chain(chain_key)
        else:
            self.chain_tainted = True
        self.layers.append(layer)
        return layer

    def replay_layer(self, ops: list, chain_key: str = "") -> Layer:
        """Fold a previously recorded applied-entry stream into the
        tree — the same tree mutations ``update_from_tar(...,
        untar=False)`` made from the blob, with zero decompression,
        zero tar parsing, and zero per-entry diffing (the record IS
        the diff outcome, valid because replay happens at the same
        layer-chain position over the same prior tree state — the
        session's digest-keyed lookup guarantees it). Per-entry cost
        drops to one tree fold, which is what makes a 100k-entry
        cached chain replay in about a second instead of several.

        Only a build that reads its tree gets here (an edit above a
        cached prefix): a fully cached build applies nothing at all."""
        self.flush()
        layer = Layer()
        for entry in ops:
            self._apply_entry(entry)
        if chain_key:
            self.extend_chain(chain_key)
        self.layers.append(layer)
        return layer

    def _skip_tar_member(self, disk_path: str, hdr: tarfile.TarInfo) -> bool:
        base = os.path.basename(disk_path)
        if base.startswith(WHITEOUT_META_PREFIX):
            return True
        if pathutils.is_descendant_of_any(disk_path, self.blacklist):
            return True
        if hdr.ischr() or hdr.isblk() or hdr.isfifo():
            return True
        return mountinfo.is_mounted(disk_path)

    def _untar_one(self, path: str, hdr: tarfile.TarInfo,
                   tf: tarfile.TarFile | None) -> tuple[int, str]:
        """Materialize one member under the root; returns the bytes of
        file content written and how it went: ``created`` (the first
        write made it) or ``probed`` (the file system was asked first).

        A file, a directory or a symlink is written without asking what
        is there: an exclusive create that fails is the answer an
        ``lexists`` would have given, and only a member that collides
        pays for the comparison with what it found. An empty root never
        does; ``FROM`` an image onto ``/`` does for most members."""
        base = os.path.basename(path)
        if base.startswith(WHITEOUT_PREFIX):
            victim = os.path.join(
                os.path.dirname(path), base[len(WHITEOUT_PREFIX):])
            if os.path.lexists(victim):
                if os.path.isdir(victim) and not os.path.islink(victim):
                    shutil.rmtree(victim, ignore_errors=True)
                else:
                    os.remove(victim)
            return 0, "probed"
        if not hdr.islnk():
            try:
                return self._untar_create(path, hdr, tf), "created"
            except FileExistsError:
                pass
        if os.path.lexists(path):
            local = tarinfo_from_stat(path, hdr.name, self.root)
            if tario.is_similar_header(local, hdr):
                return 0, "probed"
            if hdr.isdir() and local.isdir():
                # Never delete an existing dir (it may shelter mounts);
                # just update its metadata.
                tario.apply_header(path, hdr)
                return 0, "probed"
            if os.path.isdir(path) and not os.path.islink(path):
                shutil.rmtree(path)
            else:
                os.remove(path)
        return self._untar_create(path, hdr, tf), "probed"

    def _untar_create(self, path: str, hdr: tarfile.TarInfo,
                      tf: tarfile.TarFile | None) -> int:
        """Make a member where nothing is; ``FileExistsError`` where
        something is. A parent the tar never named is made on the
        create's ``FileNotFoundError``, not looked for before it."""
        try:
            return self._untar_write(path, hdr, tf)
        except FileNotFoundError:
            if hdr.islnk():
                raise  # the link's target is what is missing
            os.makedirs(os.path.dirname(path), exist_ok=True)
            return self._untar_write(path, hdr, tf)

    def _untar_write(self, path: str, hdr: tarfile.TarInfo,
                     tf: tarfile.TarFile | None) -> int:
        if hdr.isdir():
            os.mkdir(path)
            tario.apply_header(path, hdr)
        elif hdr.issym():
            target = hdr.linkname
            if os.path.isabs(target):
                target = pathutils.join_root(self.root, target)
            os.symlink(target, path)
            try:
                os.lchown(path, hdr.uid, hdr.gid)
            except PermissionError:
                pass
        elif hdr.islnk():
            os.link(pathutils.join_root(self.root, hdr.linkname), path)
        else:
            # O_EXCL follows no link: a dangling one collides too.
            fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_EXCL
                         | os.O_CLOEXEC, 0o666)
            try:
                reader = (tf.extractfile(hdr)
                          if tf is not None and hdr.size > 0 else None)
                if reader is not None:
                    # Straight to the descriptor: a file object over it
                    # starts with an fstat of the file just made.
                    while block := reader.read(_UNTAR_BLOCK):
                        view = memoryview(block)
                        while view:
                            view = view[os.write(fd, view):]
                tario.apply_header_fd(fd, hdr)
            finally:
                os.close(fd)
            return hdr.size
        return 0

    # ------------------------------------------------------------------
    # Cross-stage checkpoint / diff
    # ------------------------------------------------------------------

    def checkpoint(self, new_root: str, sources: list[str]) -> None:
        """Copy ``sources`` (globs, stage-root-relative) into ``new_root``
        preserving their paths — the sandbox the next stage's COPY --from
        reads (reference: mem_fs.go Checkpoint:91)."""
        self.flush()
        if not sources:
            return
        copied = 0
        resolved: list[str] = []
        for src in sources:
            # Sources are logical stage paths; map them under the build
            # root (identity in production where root is "/").
            pattern = pathutils.join_root(self.root, src)
            matches = glob(pattern)
            resolved.extend(matches or [pattern])
        for src in resolved:
            trimmed = pathutils.trim_root(src, self.root)
            dst = pathutils.join_root(new_root, trimmed)
            st = os.lstat(src)
            copier = fileio.Copier(
                self.blacklist,
                dir_owner=Owner(st.st_uid, st.st_gid, False))
            if os.path.isdir(src) and not os.path.islink(src):
                copier.copy_dir(src, dst)
            else:
                copier.copy_file(src, dst)
            copied += copier.bytes_copied
        metrics.counter_add(metrics.ON_DISK_BYTES_TOTAL, copied,
                            op="checkpoint")

    def compare(self, other: "MemFS", ignore_mtime: bool = True) -> FSDiff:
        self.flush()
        other.flush()
        diff = FSDiff([], [], [])

        def rec(a: Node | None, b: Node | None, path: str) -> None:
            if a is None:
                diff.missing_in_first.append(path)
                return
            if b is None:
                diff.missing_in_second.append(path)
                return
            if path != "/" and not tario.is_similar_header(
                    a.hdr, b.hdr, ignore_time=ignore_mtime):
                diff.different.append((path, a.hdr, b.hdr))
            for name in sorted(set(a.children) | set(b.children)):
                rec(a.children.get(name), b.children.get(name),
                    os.path.join(path, name))

        rec(self.tree, other.tree, "/")
        return diff
