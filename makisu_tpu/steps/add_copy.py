"""ADD/COPY steps: content-addressed cache IDs and copy operations.

Reference: lib/builder/step/add_copy_step.go (cache ID over walked file
contents SetCacheID:102, glob resolution resolveFromPaths:171, Execute
:126-150 building snapshot.CopyOperation) and add_step.go (ADD is COPY
without --from; the reference implements no URL/auto-extract support).
"""

from __future__ import annotations

import os
import stat as statmod
import zlib
from glob import glob

from makisu_tpu.context import BuildContext
from makisu_tpu.snapshot import CopyOperation, eval_symlinks
from makisu_tpu.steps.base import BuildStep
from makisu_tpu.utils import ledger, metrics, pathutils, sysutils

# Changed-file paths carried per statcache ledger decision (the blame
# list `makisu-tpu explain` prints); beyond it only the count grows.
_BLAME_KEEP = 20


class AddCopyStep(BuildStep):
    def __init__(self, directive: str, args: str, chown: str,
                 from_stage: str, srcs: list[str], dst: str,
                 commit: bool, preserve_owner: bool,
                 inline_files: list[tuple[str, str]] | None = None,
                 ordered_sources: list[tuple[str, str]] | None = None,
                 ) -> None:
        super().__init__(args, commit)
        self.directive = directive
        self.chown = chown
        self.from_stage = from_stage
        self.srcs = [s.strip("\"'") for s in srcs]
        self.dst = dst.strip("\"'")
        self.preserve_owner = preserve_owner
        # Heredoc file sources (BuildKit syntax 1.4): (name, content)
        # staged as real files at execute time, then copied with normal
        # docker semantics (a single inline file renames onto a file
        # dst; multiple require a directory dst like any other source).
        self.inline_files = list(inline_files or [])
        # Left-to-right source order (("src", path) | ("inline", name)):
        # docker applies sources in order, so later ones overwrite
        # earlier on name collisions. Default (direct construction in
        # tests): real sources then inline.
        self.ordered_sources = (list(ordered_sources)
                                if ordered_sources is not None else
                                [("src", s) for s in self.srcs]
                                + [("inline", n)
                                   for n, _ in self.inline_files])
        if len(self.srcs) + len(self.inline_files) > 1 and not (
                self.dst.endswith("/") or self.dst in (".", "..")):
            raise ValueError(
                'copying multiple sources: destination must end with "/"')

    def require_on_disk(self) -> bool:
        return bool(self.chown)

    def context_dirs(self) -> tuple[str, list[str]]:
        if not self.from_stage:
            return "", []
        return self.from_stage, list(self.srcs)

    def _source_root(self, ctx: BuildContext) -> str:
        if self.from_stage:
            return ctx.copy_from_root(self.from_stage)
        return ctx.context_dir

    def _resolve_sources(self, ctx: BuildContext,
                         srcs: list[str] | None = None) -> list[str]:
        """Glob-expand sources against the source root (absolute paths).
        Context sources matching .dockerignore are invisible — the same
        "never entered the context" semantics docker gives them."""
        root = self._source_root(ctx)
        check_ignore = not self.from_stage
        out: list[str] = []
        for src in (self.srcs if srcs is None else srcs):
            pattern = os.path.join(root, pathutils.rel_path(src))
            matches = glob(pattern)
            if check_ignore:
                visible = [m for m in matches
                           if not ctx.context_path_ignored(m)]
                if matches and not visible:
                    # Everything the pattern named is dockerignored:
                    # fail like docker does, not with an empty copy or
                    # an unexpanded-pattern stat error downstream.
                    raise ValueError(
                        f"COPY/ADD source {src!r}: all matches are "
                        "excluded by .dockerignore")
                matches = visible
            out.extend(sorted(matches) if matches else [pattern])
        return out

    def set_cache_id(self, ctx: BuildContext, seed: str) -> None:
        """Content-addressed: the cache ID covers the bytes being copied,
        so a context change invalidates exactly the right steps."""
        checksum = zlib.crc32(
            (seed + self.directive + self.args).encode())
        # Stat-cache tally for this step's context walk: which files'
        # content IDs came from the stat cache and which had to
        # re-hash (with the changed paths — the file-level blame the
        # decision ledger attaches to this step's cache ID).
        tally = {"files": 0, "hits": 0, "misses": 0,
                 "bytes_rehashed": 0, "changed": []}
        if not self.from_stage:
            # Cross-stage copies rely on chained stage cache IDs instead.
            with metrics.span("copy_checksum") as sp:
                for source in self._resolve_sources(ctx):
                    checksum = self._checksum_source(ctx, source,
                                                     checksum, tally)
                sp.set(files=tally["files"])
        for name, content in self.inline_files:
            # Inline heredoc files are content too (their bodies carry
            # substituted build args, so identity must track them).
            # Length-framed: bare concatenation would let different
            # (name, content) partitions with equal concatenations
            # collide into one cache ID.
            frame = f"{len(name)}:{len(content)}:".encode()
            checksum = zlib.crc32(frame, checksum)
            checksum = zlib.crc32(name.encode(), checksum)
            checksum = zlib.crc32(content.encode(), checksum)
        self.cache_id = format(checksum & 0xFFFFFFFF, "x")
        self._record_stat_tally(tally)

    def _record_stat_tally(self, tally: dict) -> None:
        """Flush the context-walk tally once per step (never per file —
        a 100k-file walk must not pay 100k counter locks) and record
        the step's statcache decision against its cache ID."""
        if not tally["files"]:
            return
        if tally["hits"]:
            metrics.counter_add("makisu_statcache_total", tally["hits"],
                                result="hit")
        if tally["misses"]:
            metrics.counter_add("makisu_statcache_total",
                                tally["misses"], result="miss")
        ledger.record(
            "statcache", self.cache_id,
            "hit" if not tally["misses"] else "miss",
            directive=self.directive, files=tally["files"],
            hits=tally["hits"], misses=tally["misses"],
            bytes_rehashed=tally["bytes_rehashed"],
            changed_files=list(tally["changed"]))

    def _checksum_source(self, ctx: BuildContext, source: str,
                         checksum: int, tally: dict) -> int:
        """One resolved source subtree's checksum contribution, with
        the resident session's scan memo in front: when the dirty set
        PROVES nothing under ``source`` changed, the memoized
        ``(source, checksum_in) → checksum_out`` transition replays in
        O(1) — no stat, no listdir, no crc framing. A dirtied (or
        unproven) source walks the cold path and refreshes the memo,
        so the produced cache ID is identical either way."""
        session = ctx.session
        if session is not None and ctx.source_unchanged(source):
            memo = session.scan_lookup(source, checksum)
            if memo is not None:
                checksum_out, files, _nbytes = memo
                tally["files"] += files
                tally["hits"] += files
                return checksum_out
        files_before = tally["files"]
        out = self._checksum_tree(ctx, source, checksum, tally)
        ctx.listing.flush_counts()
        if session is not None:
            session.scan_store(source, checksum, out,
                               tally["files"] - files_before, 0)
        return out

    def _checksum_tree(self, ctx: BuildContext, path: str,
                       checksum: int, tally: dict | None = None,
                       st: os.stat_result | None = None) -> int:
        """The checksum contribution of ``path`` and all below it.

        Reads the tree through the build's listing (``ctx.listing``):
        as the first pass of a build over its context this is the pass
        that fills it, one ``scandir`` a directory and ONE ``lstat`` an
        entry (kind checks read the mode bits), and the layer scan and
        the session's checkpoint replay it. Children come in the order
        of ``sorted(os.listdir)``, so cache ids do not depend on who
        listed. ``st`` is the child's stat out of its parent's listing;
        the top of a source has none yet."""
        if st is None:
            try:
                st = ctx.listing.lstat(path)
            except OSError:
                return checksum  # vanished/unstatable: as lexists=False
        if ctx.context_path_ignored(path):
            # Ignored files must not influence cache identity either —
            # editing them cannot change the build's output.
            return checksum
        if sysutils.is_special_file(st):
            return checksum
        rel = os.path.relpath(path, ctx.context_dir)
        checksum = zlib.crc32(rel.encode(), checksum)
        mode = st.st_mode
        if statmod.S_ISLNK(mode):
            return zlib.crc32(os.readlink(path).encode(), checksum)
        if statmod.S_ISDIR(mode):
            for _, child, child_st in ctx.listing.children(path):
                checksum = self._checksum_tree(ctx, child, checksum, tally,
                                               child_st)
            return checksum
        # Per-file content summary, framed into the rolling checksum.
        # The summary (not the raw byte stream) is what chains, so a
        # file's crc can come from the stat-keyed cache
        # (utils/statcache.py) and a warm rebuild re-reads only files
        # whose stat changed — identical cache IDs either way.
        file_crc, why = ctx.content_ids.lookup(rel, st)
        if tally is not None:
            tally["files"] += 1
            if why == "hit":
                tally["hits"] += 1
            else:
                tally["misses"] += 1
                tally["bytes_rehashed"] += st.st_size
                # Blame only REAL changes: a racy/disabled re-hash is a
                # perf cost, not a content change, and must not name
                # an innocent file in the explain output.
                if (why in ("absent", "stat_changed")
                        and len(tally["changed"]) < _BLAME_KEEP):
                    tally["changed"].append(rel)
        if file_crc is None:
            file_crc = 0
            with open(path, "rb") as f:
                while True:
                    chunk = f.read(1 << 20)
                    if not chunk:
                        break
                    file_crc = zlib.crc32(chunk, file_crc)
            ctx.content_ids.put(rel, st, file_crc)
        frame = f"{st.st_size}:{file_crc & 0xFFFFFFFF:08x};".encode()
        return zlib.crc32(frame, checksum)

    def _stage_inline_files(self, ctx: BuildContext) -> str:
        """Write heredoc bodies as real files in the build sandbox (they
        must outlive execute: the MemFS copy-op diff reads file bytes at
        commit time). The staging dir is keyed by cache_id so steps
        never collide. UTF-8 explicitly — cache identity hashed
        content.encode(), the bytes on disk must match regardless of
        host locale."""
        stage_dir = os.path.join(ctx.image_store.sandbox_dir,
                                 "heredocs", self.cache_id or "x")
        os.makedirs(stage_dir, exist_ok=True)
        for name, content in self.inline_files:
            path = os.path.join(stage_dir, name)
            with open(path, "w", encoding="utf-8") as f:
                f.write(content)
            os.chmod(path, 0o644)
            # Epoch mtime: generated files carry no meaningful
            # timestamp, and a deterministic one makes heredoc layers
            # byte-reproducible across rebuilds (a live mtime would
            # change the layer tar's bytes every build) AND keeps the
            # header-similarity diff from ever confusing a staged file
            # with a same-sized real source written the same second.
            os.utime(path, (0, 0))
        return stage_dir

    def execute(self, ctx: BuildContext, modify_fs: bool) -> None:
        blacklist = list(ctx.base_blacklist) + [ctx.image_store.root]
        stage_dir = (self._stage_inline_files(ctx)
                     if self.inline_files else "")
        # One CopyOperation per consecutive run of same-kind sources,
        # in the line's left-to-right order: docker applies sources in
        # order, so a later source overwrites an earlier one on a name
        # collision — real files and inline heredocs interleave.
        runs: list[tuple[str, list[str]]] = []
        for kind, val in self.ordered_sources:
            if runs and runs[-1][0] == kind:
                runs[-1][1].append(val)
            else:
                runs.append((kind, [val]))
        if not runs:
            runs = [("src", [])]  # preserve empty-sources error path
        inline_contents = dict(self.inline_files)
        for kind, vals in runs:
            if kind == "src":
                source_root = self._source_root(ctx)
                rel_paths = [
                    pathutils.trim_root(s, source_root)
                    for s in self._resolve_sources(ctx, srcs=vals)]
                ctx_blacklist = list(blacklist)
                if not self.from_stage:
                    # .dockerignore exclusions ride the blacklist, which
                    # both the on-disk Copier and the MemFS copy-op diff
                    # honor.
                    ctx_blacklist += ctx.context_excluded_paths()
                op = CopyOperation(
                    rel_paths, source_root, self.logical_working_dir,
                    self.dst, chown=self.chown, blacklist=ctx_blacklist,
                    internal=bool(self.from_stage),
                    preserve_owner=self.preserve_owner)
            else:
                assert all(v in inline_contents for v in vals)
                op = CopyOperation(
                    vals, stage_dir, self.logical_working_dir, self.dst,
                    chown=self.chown, blacklist=blacklist,
                    internal=True, preserve_owner=self.preserve_owner)
            ctx.copy_ops.append(op)
            if modify_fs:
                with metrics.span("copy_on_disk") as sp:
                    files, nbytes = op.execute(eval_symlinks, ctx.root_dir)
                    sp.set(files=files, bytes=nbytes)
                metrics.counter_add(metrics.ON_DISK_BYTES_TOTAL, nbytes,
                                    op="copy")


class AddStep(AddCopyStep):
    def __init__(self, args: str, chown: str, srcs: list[str], dst: str,
                 commit: bool, preserve_owner: bool,
                 inline_files: list[tuple[str, str]] | None = None,
                 ordered_sources: list[tuple[str, str]] | None = None,
                 ) -> None:
        super().__init__("ADD", args, chown, "", srcs, dst, commit,
                         preserve_owner, inline_files, ordered_sources)


class CopyStep(AddCopyStep):
    def __init__(self, args: str, chown: str, from_stage: str,
                 srcs: list[str], dst: str, commit: bool,
                 preserve_owner: bool,
                 inline_files: list[tuple[str, str]] | None = None,
                 ordered_sources: list[tuple[str, str]] | None = None,
                 ) -> None:
        super().__init__("COPY", args, chown, from_stage, srcs, dst, commit,
                         preserve_owner, inline_files, ordered_sources)
