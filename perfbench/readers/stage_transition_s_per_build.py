"""Seconds a counted build spent between its stages under
``--modifyfs``: copying what later stages take with ``COPY --from``
into the sandbox (span ``stage_checkpoint``) and wiping the stage's
tree under ``--root`` (span ``stage_cleanup``), both under ``stage``."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "stage_checkpoint",
                                            "stage_cleanup")
