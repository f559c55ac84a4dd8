"""FROM step: establish the base image.

Reference: lib/builder/step/from_step.go (Execute:94-137 applies base
layers to MemFS; Commit:139 returns the base DigestPairs when the stage is
copied-from; UpdateCtxAndConfig seeds config + stage vars from the base).
"""

from __future__ import annotations

from makisu_tpu import tario
from makisu_tpu.context import BuildContext
from makisu_tpu.docker.image import (
    Digest,
    DigestPair,
    DistributionManifest,
    ImageConfig,
    ImageName,
)
from makisu_tpu.steps.base import BuildStep, chain_cache_id
from makisu_tpu.utils import logging as log


class FromStep(BuildStep):
    directive = "FROM"

    def __init__(self, args: str, image: str, alias: str) -> None:
        super().__init__(args, commit=False)
        if image.lower() != "scratch":
            image = str(ImageName.parse_for_pull(image))
        self.image = image
        self.alias = alias
        self.registry_client = None  # injected by the plan
        self._manifest: DistributionManifest | None = None
        self._config: ImageConfig | None = None
        # Pipelined pull in flight (clients exposing start_pull): layer
        # downloads run ahead on the transfer engine while execute()
        # applies layers strictly in manifest order.
        self._pull_handle = None

    @property
    def is_scratch(self) -> bool:
        return self.image.lower() == "scratch"

    def set_cache_id(self, ctx: BuildContext, seed: str) -> None:
        import os
        # An explicit platform pin changes what a multi-arch tag
        # resolves to, so it must be part of the cache identity — two
        # platforms of one tag must never share layer-cache entries.
        # Only chained when set: the unset default keeps pre-existing
        # cache ids valid.
        platform = os.environ.get("MAKISU_TPU_PLATFORM", "")
        parts = [self.directive, self.image]
        if platform:
            parts.append(platform)
        self.cache_id = chain_cache_id(seed, *parts)

    @staticmethod
    def _platform_matches(config: ImageConfig, want: str) -> bool:
        parts = want.split("/")
        want_os, want_arch = parts[0], parts[1] if len(parts) > 1 else ""
        return config.os == want_os and config.architecture == want_arch

    def _load(self, ctx: BuildContext) -> None:
        import os
        if self._manifest is not None:
            return
        name = ImageName.parse(self.image)
        store = ctx.image_store
        want_platform = os.environ.get("MAKISU_TPU_PLATFORM", "")

        def read_config(manifest) -> ImageConfig:
            with store.layers.open(manifest.config.digest.hex()) as f:
                return ImageConfig.from_bytes(f.read())

        manifest = config = None
        if store.manifests.exists(name):
            manifest = store.manifests.load(name)
            config = read_config(manifest)
            if want_platform and not self._platform_matches(
                    config, want_platform):
                # The locally cached manifest was resolved for another
                # platform (multi-arch tag pulled before the pin
                # changed): it must not be silently reused.
                log.info("cached %s is %s/%s; re-pulling for %s",
                         self.image, config.os, config.architecture,
                         want_platform)
                manifest = config = None
        try:
            if manifest is None:
                if self.registry_client is None:
                    raise RuntimeError(
                        f"no registry client to pull base image "
                        f"{self.image}")
                start_pull = getattr(self.registry_client, "start_pull",
                                     None)
                if start_pull is not None:
                    # Pipelined: manifest + config arrive now, layer
                    # blobs keep downloading while execute() extracts
                    # in order.
                    self._pull_handle = start_pull(name)
                    manifest = self._pull_handle.manifest
                else:
                    manifest = self.registry_client.pull(name)
                config = read_config(manifest)
                if want_platform and not self._platform_matches(
                        config, want_platform):
                    raise ValueError(
                        f"base image {self.image} is "
                        f"{config.os}/{config.architecture}, but "
                        f"MAKISU_TPU_PLATFORM wants {want_platform}")
            self._manifest = manifest
            self._config = config
            if len(self._config.rootfs.diff_ids) != len(manifest.layers):
                raise ValueError(
                    "base image layer count mismatch between config and "
                    "manifest")
        except BaseException:
            # Any validation failure (unparseable config, platform or
            # layer-count mismatch) must settle the in-flight pipelined
            # pull — a failed build must not keep downloading layers on
            # the engine capacity other builds share.
            self._abandon_pull()
            raise

    def execute(self, ctx: BuildContext, modify_fs: bool) -> None:
        if self.is_scratch:
            log.info("scratch base image; nothing to apply")
            return
        self._load(ctx)
        assert self._manifest is not None
        try:
            for descriptor in self._manifest.layers:
                if self._pull_handle is not None:
                    # Gate on THIS layer only: extraction of layer k
                    # overlaps the wire time of layers k+1..
                    # (application must stay in manifest order — each
                    # layer's whiteouts overwrite the previous one's
                    # state).
                    self._pull_handle.wait_layer(descriptor.digest)
                log.info("applying FROM layer %s", descriptor.digest.hex())
                with ctx.image_store.layers.open(
                        descriptor.digest.hex()) as f:
                    with tario.gzip_reader(f) as gz, \
                            tario.layer_tar(gz) as tf:
                        # chain_key keeps the applied-chain identity
                        # intact, so cached layers ABOVE this base
                        # stay replay-memoizable.
                        ctx.memfs.update_from_tar(
                            tf, untar=modify_fs,
                            chain_key=descriptor.digest.hex())
        except BaseException:
            self._abandon_pull()
            raise
        self._finish_pull()

    def _abandon_pull(self) -> None:
        """The build failed mid-FROM: settle the in-flight pull without
        masking the original error (queued downloads cancel, running
        ones join, their errors are swallowed)."""
        handle, self._pull_handle = self._pull_handle, None
        if handle is not None:
            handle.abandon()

    def _finish_pull(self) -> None:
        """Join any still-running downloads and save the manifest (a
        no-op once done). Kept separate from execute so commit() can
        settle the pull even on paths that never applied the layers."""
        if self._pull_handle is not None:
            self._pull_handle.wait_all()
            self._pull_handle = None

    def commit(self, ctx: BuildContext) -> list[DigestPair]:
        if self.is_scratch:
            return []
        self._load(ctx)
        self._finish_pull()
        assert self._manifest is not None and self._config is not None
        return [
            DigestPair(Digest(diff_id), desc)
            for diff_id, desc in zip(self._config.rootfs.diff_ids,
                                     self._manifest.layers)
        ]

    def update_ctx_and_config(self, ctx: BuildContext,
                              config: ImageConfig | None) -> ImageConfig:
        if self.is_scratch:
            return ImageConfig()
        self._load(ctx)
        assert self._config is not None
        for kv in self._config.config.env:
            key, _, val = kv.partition("=")
            ctx.stage_vars[key] = val
        return self._config.clone()
