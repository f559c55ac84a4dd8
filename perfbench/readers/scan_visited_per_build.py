"""Entries of the root a counted build's scans visited, each an
``lstat``, a tar header and a header compare: growth of
``makisu_scan_entries_total{result="visited"}`` (``MemFS.
add_layer_by_scan`` adds one count a layer). About the root's entry
count for each layer a ``RUN`` closes."""
from pbharness import hostspans


def read(run):
    return hostspans.counter_per_build(run, "makisu_scan_entries_total",
                                       result="visited")
