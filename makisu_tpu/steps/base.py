"""BuildStep base: cache-ID chaining and the layer-commit path.

Reference: lib/builder/step/base_step.go (crc32 chaining :62-67, workdir/env
setup :71-117) and common.go (commitLayer:67, tarAndGzipDiffs:35). The
commit path here streams the layer tar through the context's chunker.Hasher
seam instead of hand-wired digest fan-outs — that one line is where the TPU
backend plugs in.
"""

from __future__ import annotations

import os
import tempfile
import zlib

from makisu_tpu.chunker.hasher import LayerCommit
from makisu_tpu.context import BuildContext
from makisu_tpu.docker.image import DigestPair, ImageConfig
from makisu_tpu.utils import logging as log
from makisu_tpu.utils import metrics


def chain_cache_id(seed: str, *parts: str) -> str:
    """crc32 over seed+parts, hex — the chained per-step cache identity
    (reference: base_step.go SetCacheID)."""
    payload = (seed + "".join(parts)).encode()
    return format(zlib.crc32(payload) & 0xFFFFFFFF, "x")


class BuildStep:
    """One executable Dockerfile directive.

    Lifecycle per node: apply_ctx_and_config → (apply cached layers) →
    execute → commit → update_ctx_and_config. Metadata-only steps override
    just ``update_config``.
    """

    directive = "STEP"

    def __init__(self, args: str, commit: bool) -> None:
        self.args = args
        self.commit_annotation = commit
        self.cache_id = ""
        self.working_dir = "/"
        self.logical_working_dir = "/"
        # Chunk fingerprints of layers committed by this step (TPU hasher);
        # consumed by the chunk-dedup cache.
        self.layer_commits: list[LayerCommit] = []

    # -- identity ---------------------------------------------------------

    def __str__(self) -> str:
        suffix = " #!COMMIT" if self.commit_annotation else ""
        return f"{self.directive} {self.args}{suffix} ({self.cache_id})"

    def has_commit(self) -> bool:
        return self.commit_annotation

    def set_cache_id(self, ctx: BuildContext, seed: str) -> None:
        self.cache_id = chain_cache_id(
            seed, self.directive, self.args, str(self.commit_annotation))

    # -- capabilities -----------------------------------------------------

    def require_on_disk(self) -> bool:
        return False

    def context_dirs(self) -> tuple[str, list[str]]:
        """(stage alias, dirs) this step needs from another stage."""
        return "", []

    # -- lifecycle --------------------------------------------------------

    def apply_ctx_and_config(self, ctx: BuildContext,
                             config: ImageConfig | None) -> None:
        self._set_working_dir(ctx, config)
        self._export_stage_vars(ctx)

    def execute(self, ctx: BuildContext, modify_fs: bool) -> None:
        pass

    def commit(self, ctx: BuildContext) -> list[DigestPair]:
        return commit_layer(ctx, self)

    def update_ctx_and_config(self, ctx: BuildContext,
                              config: ImageConfig | None) -> ImageConfig:
        base = config.clone() if config is not None else ImageConfig()
        return self.update_config(ctx, base)

    def update_config(self, ctx: BuildContext,
                      config: ImageConfig) -> ImageConfig:
        return config

    # -- helpers ----------------------------------------------------------

    def _set_working_dir(self, ctx: BuildContext,
                         config: ImageConfig | None) -> None:
        from makisu_tpu.utils import pathutils
        # Logical working dir (image-config space) for copy destinations;
        # physical working dir (under the build root) for exec'd commands.
        # Identical in production where root is "/".
        self.logical_working_dir = "/"
        self.working_dir = ctx.root_dir
        if config is not None and config.config.working_dir:
            from makisu_tpu.utils import envutils
            self.logical_working_dir = envutils.expand(
                config.config.working_dir, ctx.exec_env)
            self.working_dir = pathutils.join_root(ctx.root_dir,
                                                   self.logical_working_dir)
        if not os.path.lexists(self.working_dir):
            os.makedirs(self.working_dir, exist_ok=True)

    def _export_stage_vars(self, ctx: BuildContext) -> None:
        """ARG/ENV values become the RUN-step env — the build-local
        exec_env, never os.environ (concurrent builds share a process)."""
        from makisu_tpu.utils import envutils
        for key, value in ctx.stage_vars.items():
            if len(value) >= 2 and value[0] == value[-1] == '"':
                value = value[1:-1]
            ctx.exec_env[key] = envutils.expand(value, ctx.exec_env)


def commit_layer(ctx: BuildContext, step: BuildStep) -> list[DigestPair]:
    """Generate one layer from the context's pending changes.

    Scan-diff after RUN (must_scan), copy-op diff after ADD/COPY, or
    nothing. The tar stream flows through ctx.hasher — the CPU/TPU seam —
    and the gzipped blob lands in the layer CAS store.
    """
    if ctx.must_scan:
        write_diffs = ctx.memfs.add_layer_by_scan
    elif ctx.copy_ops:
        ops = ctx.copy_ops

        def write_diffs(tw):
            return ctx.memfs.add_layer_by_copy_ops(ops, tw, ctx.listing)
    else:
        return []

    fd, tmp = tempfile.mkstemp(dir=ctx.image_store.sandbox_dir,
                               prefix="layertar-")
    try:
        with metrics.span("commit_layer", directive=step.directive):
            with os.fdopen(fd, "wb") as out:
                sink = ctx.hasher.open_layer(out,
                                             backend_id=ctx.gzip_backend_id)
                with sink.open_tar() as tw:
                    write_diffs(tw)
                # Device drain and readbacks, chunk-SHA tail, gzip tail.
                with metrics.span("sink_finish") as sp:
                    layer_commit = sink.finish()
                    sp.set(chunks=len(layer_commit.chunks))
            pair = layer_commit.digest_pair
            ctx.image_store.layers.link_file(
                pair.gzip_descriptor.digest.hex(), tmp)
            step.layer_commits.append(layer_commit)
    finally:
        os.unlink(tmp)
    ctx.must_scan = False
    ctx.copy_ops = []
    metrics.counter_add("makisu_layer_commits_total")
    metrics.counter_add("makisu_layer_bytes_total",
                        pair.gzip_descriptor.size)
    metrics.counter_add("makisu_layer_chunks_total",
                        len(layer_commit.chunks))
    log.info("committed layer %s (%d bytes, %d chunks)",
             pair.gzip_descriptor.digest, pair.gzip_descriptor.size,
             len(layer_commit.chunks))
    return [pair]
