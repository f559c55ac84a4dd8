"""Whiteouts a counted build's scans wrote, one for each path a lower
layer holds that a ``RUN`` removed: growth of
``makisu_scan_entries_total{result="whiteout"}``. 0 where the window's
scans found nothing gone; nothing where the program has no such
counter."""
from pbharness import hostspans

_SERIES = "makisu_scan_entries_total"


def read(run):
    if hostspans.counter_per_build(run, _SERIES) is None:
        return None
    return hostspans.counter_per_build(run, _SERIES, result="whiteout") or 0.0
