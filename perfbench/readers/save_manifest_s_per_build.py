"""Seconds a counted build spent writing its image's config blob and
manifest, once for the tag and once a replica (span
``save_manifest{replicas}``, under ``build``, after the pushes are
joined)."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "save_manifest")
