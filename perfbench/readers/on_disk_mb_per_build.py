"""MB a counted build wrote under ``--root`` and into the sandbox for
its stages' trees: growth of ``makisu_on_disk_bytes_total``, all three
``op``s (``copy``: a ``COPY`` executed on disk; ``untar``: a cached
layer unpacked under the root; ``checkpoint``: what later stages copy
from, copied into the sandbox)."""
from pbharness import hostspans


def read(run):
    grown = hostspans.counter_per_build(run, "makisu_on_disk_bytes_total")
    return None if grown is None else grown / 1e6
