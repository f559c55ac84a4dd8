"""Of the chunk bytes the window's builds offered to the chunk store,
the share that was new (``makisu_chunk_bytes_total{result="added"}``
over added plus reused)."""
from pbharness import stats


def read(run):
    if run.counters_open is None:
        return None
    added = stats.counter_delta(run.counters_open, run.counters_close,
                                "makisu_chunk_bytes_total", result="added")
    reused = stats.counter_delta(run.counters_open, run.counters_close,
                                 "makisu_chunk_bytes_total", result="reused")
    if added + reused <= 0:
        return None
    return 100.0 * added / (added + reused)
