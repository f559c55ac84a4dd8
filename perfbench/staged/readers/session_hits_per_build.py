"""Builds that met a live resident session whose dirty set was exact,
per counted build (``makisu_session_hits``, one add a
``BuildSession.begin_build`` that found ``exact``): 1.0 where every
counted build met the session the build before it left, 0.0 where each
names a ``--root`` of its own (``root`` is in a session's identity).
A worker that never counted a hit exports no such series: where
sessions began builds (``makisu_session_dirty_paths_total`` is there)
that is 0.0, and ``None`` only from a run without counters."""
from pbharness import hostspans


def read(run):
    hits = hostspans.counter_per_build(run, "makisu_session_hits")
    if hits is None and hostspans.counter_per_build(
            run, "makisu_session_dirty_paths_total") is not None:
        return 0.0
    return hits
