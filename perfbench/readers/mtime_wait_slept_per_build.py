"""Committed layers per counted build that slept out tar's one-second
mtime granularity: growth of ``makisu_mtime_wait_total{result="slept"}``
(``MemFS._wait_out_mtime`` adds one a layer, ``slept`` or ``clear``).
0 where every layer of the window was ``clear``; nothing where the
program has no such counter."""
from pbharness import hostspans

_SERIES = "makisu_mtime_wait_total"


def read(run):
    if hostspans.counter_per_build(run, _SERIES) is None:
        return None
    return hostspans.counter_per_build(run, _SERIES, result="slept") or 0.0
