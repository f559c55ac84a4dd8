"""RUN step: shell out, then mark the FS for scanning.

Reference: lib/builder/step/run_step.go (RequireOnDisk:46, Execute:63-71).
"""

from __future__ import annotations

import subprocess

from makisu_tpu import shell
from makisu_tpu.context import BuildContext
from makisu_tpu.docker.image import ImageConfig
from makisu_tpu.steps.base import BuildStep
from makisu_tpu.utils import metrics


class RunStep(BuildStep):
    directive = "RUN"

    def __init__(self, args: str, cmd: str, commit: bool) -> None:
        super().__init__(args, commit)
        self.cmd = cmd
        self.user = ""

    def require_on_disk(self) -> bool:
        return True

    def apply_ctx_and_config(self, ctx: BuildContext,
                             config: ImageConfig | None) -> None:
        super().apply_ctx_and_config(ctx, config)
        if config is not None:
            self.user = config.config.user

    def execute(self, ctx: BuildContext, modify_fs: bool) -> None:
        if not modify_fs:
            raise RuntimeError(
                "RUN step requires a modifiable filesystem (--modifyfs)")
        ctx.must_scan = True
        # The command may write anywhere, the context included: what
        # the build has listed of it is no longer what is on disk.
        ctx.listing.close()
        # The command's seconds, fork to exit, apart from `step`'s own.
        with metrics.span("run_exec") as sp:
            try:
                shell.exec_command(self.working_dir, self.user, "sh", "-c",
                                   self.cmd, env=ctx.exec_env)
            except subprocess.CalledProcessError as e:
                sp.set(exit=e.returncode)
                raise
            sp.set(exit=0)
