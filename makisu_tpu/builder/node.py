"""buildNode: per-step build lifecycle.

Reference: lib/builder/build_node.go (Build:62-100, doCommit:102,
applyLayer:133, push/pullCacheLayer:151-181).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools

from makisu_tpu import tario
from makisu_tpu.context import BuildContext
from makisu_tpu.docker.image import DigestPair, ImageConfig
from makisu_tpu.steps import BuildStep
from makisu_tpu.utils import logging as log
from makisu_tpu.utils import metrics


@dataclasses.dataclass
class NodeOptions:
    skip_build: bool = False
    force_commit: bool = False
    modify_fs: bool = False

    def __str__(self) -> str:
        parts = [name for name, on in (
            ("skip", self.skip_build), ("commit", self.force_commit),
            ("modifyfs", self.modify_fs)) if on]
        return ",".join(parts)


class BuildNode:
    def __init__(self, ctx: BuildContext, step: BuildStep) -> None:
        self.ctx = ctx
        self.step = step
        self.digest_pairs: list[DigestPair] | None = None  # None = uncached

    def __str__(self) -> str:
        return str(self.step)

    @property
    def cache_id(self) -> str:
        return self.step.cache_id

    def has_commit(self) -> bool:
        return self.step.has_commit()

    def build(self, cache_mgr, prev_config: ImageConfig | None,
              opts: NodeOptions) -> ImageConfig:
        self.step.apply_ctx_and_config(self.ctx, prev_config)
        cached = self.digest_pairs is not None
        if cached:
            # The tree has readers only if a later step commits; the
            # MemFS applies these, in order, when one does. A RUN or a
            # COPY --from reads the disk instead, at any moment.
            memfs = self.ctx.memfs
            for pair in self.digest_pairs:
                memfs.defer(pair.gzip_descriptor.digest.hex(),
                            functools.partial(self._apply_layer, pair,
                                              opts.modify_fs, cache_mgr))
            if opts.modify_fs:
                memfs.flush()
        if opts.skip_build:
            log.info("skipping execution; a later step was cached")
        elif cached:
            log.info("skipping execution; step was cached")
        else:
            self.step.execute(self.ctx, opts.modify_fs)
            if self.step.has_commit() or opts.force_commit:
                self._do_commit(cache_mgr)
            else:
                log.info("not committing step %s", self.step)
        return self.step.update_ctx_and_config(self.ctx, prev_config)

    def _do_commit(self, cache_mgr) -> None:
        self.digest_pairs = self.step.commit(self.ctx)
        # Multi-layer commits (FROM of a copied-from stage) cannot map to
        # one cache entry; skip the cache for those.
        if len(self.digest_pairs) > 1:
            return
        pair = self.digest_pairs[0] if self.digest_pairs else None
        commit = self.step.layer_commits[-1] if self.step.layer_commits else None
        log.info("pushing cache id %s", self.cache_id)
        cache_mgr.push_cache(self.cache_id, pair, commit)

    def _apply_layer(self, pair: DigestPair, modify_fs: bool,
                     cache_mgr=None) -> None:
        """Fold one cached layer into the MemFS tree, and unpack it
        under the root with ``modify_fs``. Runs from ``MemFS.flush``
        alone: at once with ``modify_fs``, else when a later step of
        the stage reads the tree, and never in a stage where none does
        (``BuildStage.build`` drops it and counts it ``unread``)."""
        hex_digest = pair.gzip_descriptor.digest.hex()
        # Resident-session fast path: a layer this session has already
        # folded into a MemFS tree at this exact chain position replays
        # from its recorded applied-entry stream — no blob open, no
        # gzip inflate, no tar parse, no per-entry diff. The memo keys
        # on (applied-chain, digest): the recorded ops bake in the
        # prior tree state's diff outcome, so the same blob applied at
        # a different position (Dockerfile reorder) records fresh
        # instead of replaying stale state. Only for in-memory
        # application (modify_fs must hit the disk), and only on an
        # untainted chain (every prior layer named itself). It serves
        # partially cached builds: a fully cached one applies nothing.
        memfs = self.ctx.memfs
        session = getattr(self.ctx, "session", None)
        memo_ok = (session is not None and not modify_fs
                   and not memfs.chain_tainted)
        if memo_ok:
            memo_key = (memfs.applied_chain, hex_digest)
            ops = session.replay_lookup(memo_key)
            if ops is not None:
                log.info("replaying resident layer %s (%d entries)",
                         hex_digest[:12], len(ops))
                with metrics.span("apply_layer",
                                  digest=hex_digest[:12], replay=True):
                    memfs.replay_layer(ops, chain_key=hex_digest)
                metrics.counter_add(
                    metrics.CACHED_LAYERS_APPLIED_TOTAL)
                metrics.counter_add(metrics.LAYER_REPLAY_TOTAL,
                                    result="memo")
                return
        log.info("applying cached layer %s (unpack=%s)", hex_digest,
                 modify_fs)
        record = [] if memo_ok else None
        # Application consumes the UNCOMPRESSED tar stream; route it
        # through the cache manager when it can supply one — with chunk
        # dedup attached, a lazily-pulled layer streams straight from
        # local chunks (no blob transfer, no gzip inflate at all).
        with metrics.span("apply_layer", digest=hex_digest[:12],
                          untar=modify_fs), \
                metrics.span("apply_layer.inflate") as inflate, \
                contextlib.ExitStack() as stack:
            open_tar = getattr(cache_mgr, "open_layer_tar", None)
            if open_tar is not None:
                stream = stack.enter_context(open_tar(pair))
            else:
                stream = stack.enter_context(tario.gzip_reader(
                    stack.enter_context(
                        self.ctx.image_store.layers.open(hex_digest))))
            with tario.layer_tar(stream) as tf:
                memfs.update_from_tar(tf, untar=modify_fs, record=record,
                                      chain_key=hex_digest)
            # decompress calls the blob took: tens a layer, a block
            # each; 0 where the stream was not gzip (the chunk route).
            reads = getattr(stream, "reads", 0)
            inflate.set(reads=reads)
        if record is not None:
            session.replay_store(memo_key, record)
        # After the span: a failed application must not count.
        metrics.counter_add(metrics.CACHED_LAYERS_APPLIED_TOTAL)
        metrics.counter_add(metrics.LAYER_REPLAY_TOTAL, result="inflate")
        metrics.counter_add(metrics.LAYER_INFLATE_READS_TOTAL, reads)

    def pull_cache_layer(self, cache_mgr) -> bool:
        """Try to prefetch this node's layer. A miss or failure returns
        False and breaks the stage's prefetch chain; the EMPTY sentinel
        (None) continues it (reference :166-181)."""
        from makisu_tpu.cache.manager import CacheMiss
        try:
            pair = cache_mgr.pull_cache(self.cache_id)
        except CacheMiss:
            return False
        except Exception as e:  # noqa: BLE001 - network path
            log.error("failed to fetch cache layer %s: %s", self.cache_id, e)
            return False
        if pair is None:
            self.digest_pairs = []  # sentinel: counts as fetched, no layer
            return True
        self.digest_pairs = [pair]
        return True
