"""Seconds a counted build spent executing its ``COPY``s on disk under
``--root`` (span ``copy_on_disk``, under ``step``: a stage that is
copied from, or that has a ``RUN``, copies for real before it
commits)."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "copy_on_disk")
