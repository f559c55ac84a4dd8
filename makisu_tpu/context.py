"""BuildContext: shared state threaded through steps of one stage.

Reference: lib/context/build_context.go:35-88. Adds one field the reference
lacks: the ``hasher`` seam (chunker.Hasher) every committed layer streams
through — the TPU/CPU backend selection point.
"""

from __future__ import annotations

import base64
import os

from makisu_tpu.chunker import CPUHasher, Hasher
from makisu_tpu.snapshot import MemFS, TreeListing
from makisu_tpu.storage import ImageStore
from makisu_tpu.utils import pathutils

_STAGES_DIR = "stages"


class BuildContext:
    def __init__(self, root_dir: str, context_dir: str,
                 image_store: ImageStore,
                 hasher: Hasher | None = None,
                 blacklist: list[str] | None = None,
                 sync_wait: float | None = None,
                 gzip_backend_id: str | None = None) -> None:
        self.root_dir = root_dir
        self.context_dir = context_dir
        self.image_store = image_store
        self.stage_vars: dict[str, str] = {}
        self.copy_ops = []
        self.must_scan = False
        # Per-build process environment for RUN steps. ARG/ENV exports
        # land here, never in os.environ — concurrent builds in one
        # worker process must not see each other's variables. Each stage
        # starts from the snapshot taken at build start (the reference
        # restores os.environ between stages, build_plan.go:197-204).
        self._base_env: dict[str, str] = dict(os.environ)
        self.exec_env: dict[str, str] = dict(self._base_env)
        # Per-build compression identity (tario.make_backend_id); None
        # falls back to the process default. Lives here, not in tario's
        # globals, so concurrent builds with different flags don't race.
        self.gzip_backend_id = gzip_backend_id
        self.hasher = hasher or CPUHasher()
        self.stages_dir = os.path.join(image_store.sandbox_dir, _STAGES_DIR)
        os.makedirs(self.stages_dir, exist_ok=True)
        if blacklist is None:
            blacklist = list(pathutils.DEFAULT_BLACKLIST)
        # Without the build-internal dirs: copy-op sources legitimately
        # live in the context dir, so steps extend this base themselves.
        self.base_blacklist = list(blacklist)
        self.blacklist = blacklist + [context_dir, image_store.root]
        kwargs = {} if sync_wait is None else {"sync_wait": sync_wait}
        self.memfs = MemFS(root_dir, self.blacklist, **kwargs)
        # .dockerignore (capability beyond the reference): the excluded
        # path set is computed lazily on first context COPY/ADD and
        # cached for the build.
        self._ignore_excluded: list[str] | None = None
        self._ignore_prefixes = None  # PrefixSet over _ignore_excluded
        # Stat-keyed content-ID cache (utils/statcache.py): warm builds
        # skip re-reading context files whose (size, mtime, ctime,
        # inode) is unchanged. Lives in the storage dir beside the KV
        # cache; BuildPlan.execute saves it.
        from makisu_tpu.utils.statcache import ContentIDCache
        self.content_ids = ContentIDCache(
            os.path.join(image_store.root, "content_id_cache.json"),
            namespace=os.path.abspath(context_dir))
        # Resident build session (worker/session.py), armed by
        # session.begin_build for warm rebuilds: dirty_paths is the set
        # of context paths that changed since the last build of this
        # context, dirty_exact says whether that set provably covers
        # every change (only then may scans be skipped).
        self.session = None
        self.dirty_paths: frozenset[str] = frozenset()
        self.dirty_exact = False
        # This build's one listing of its context tree
        # (snapshot/walk.py): the checksum pass, the layer scan and the
        # session's checkpoint stat each entry once between them.
        self.listing = TreeListing(context_dir)

    def source_unchanged(self, path: str) -> bool:
        """True when the resident session PROVES nothing under ``path``
        changed since the last build: the dirty set is exact and no
        dirty path is ``path``, below it, or an ANCESTOR of it (a
        renamed/moved parent dirties every source inside it even when
        the watcher only evented the parent itself). Gate for every
        scan-memo shortcut — when this is False, the full walk runs
        (cold-path semantics, cold-path results)."""
        if not self.dirty_exact:
            return False
        prefix = path.rstrip("/") + "/"
        for dirty in self.dirty_paths:
            if (dirty == path or dirty.startswith(prefix)
                    or prefix.startswith(dirty.rstrip("/") + "/")):
                return False
        return True

    def context_excluded_paths(self) -> list[str]:
        """Absolute context paths excluded by .dockerignore (empty when
        the file is absent)."""
        if self._ignore_excluded is None:
            from makisu_tpu.utils.dockerignore import DockerIgnore, PrefixSet
            ignore = DockerIgnore.load(self.context_dir)
            self._ignore_excluded = (
                ignore.excluded_paths(self.context_dir) if ignore else [])
            self._ignore_prefixes = PrefixSet(self._ignore_excluded)
            if self._ignore_excluded:
                from makisu_tpu.utils import logging as log
                log.info(".dockerignore excludes %d context paths",
                         len(self._ignore_excluded))
        return self._ignore_excluded

    def context_path_ignored(self, path: str) -> bool:
        """O(log n) .dockerignore probe (the checksum/copy walks call
        this once per context path)."""
        self.context_excluded_paths()
        return self._ignore_prefixes.covers(path)

    def copy_from_root(self, alias: str) -> str:
        """Sandbox dir holding stage ``alias``'s checkpointed files for
        COPY --from (reference: CopyFromRoot build_context.go:83)."""
        dirname = base64.urlsafe_b64encode(alias.encode()).decode()
        return os.path.join(self.stages_dir, dirname)

    def new_stage_context(self) -> "BuildContext":
        """Fresh per-stage context sharing the store and root (the MemFS
        restarts empty each stage)."""
        ctx = BuildContext.__new__(BuildContext)
        ctx.root_dir = self.root_dir
        ctx.context_dir = self.context_dir
        ctx.image_store = self.image_store
        ctx.stage_vars = {}
        ctx.copy_ops = []
        ctx.must_scan = False
        ctx._base_env = self._base_env
        ctx.exec_env = dict(self._base_env)
        ctx.gzip_backend_id = self.gzip_backend_id
        ctx.hasher = self.hasher
        ctx.stages_dir = self.stages_dir
        ctx.base_blacklist = self.base_blacklist
        ctx.blacklist = self.blacklist
        ctx.memfs = MemFS(self.root_dir, self.blacklist,
                          sync_wait=self.memfs.sync_wait)
        ctx._ignore_excluded = self._ignore_excluded
        ctx._ignore_prefixes = self._ignore_prefixes
        # SHARED, not fresh: stages hash the same context files, and
        # the plan saves the base context's cache once at the end.
        ctx.content_ids = self.content_ids
        # Session state is shared too: every stage scans the same
        # context tree under the same dirty set.
        ctx.session = self.session
        ctx.dirty_paths = self.dirty_paths
        ctx.dirty_exact = self.dirty_exact
        # One context tree, one listing: shared by reference.
        ctx.listing = self.listing
        return ctx
