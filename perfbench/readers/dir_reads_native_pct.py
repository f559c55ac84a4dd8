"""Of the directories the window's builds read from disk (a context
listing that is not a replay, any other walk, the session watcher's
descent: ``snapshot/walk.py:_read_dir``), the share read by the native
reader's one foreign call, the interpreter lock handed back once a
directory: growth of ``makisu_dir_reads_total{route="native"}`` over
the growth of both routes (``python`` = ``scandir``, the lock handed
back at every ``readdir`` and ``lstat``), ``stat`` 1 and 0 together.
100 where ``libdirscan.so`` was built, 0 where it was not. ``None``
where nothing was read, from an untraced run and from a program
without the series."""
from pbharness import stats

_SERIES = "makisu_dir_reads_total"


def read(run):
    if run.counters_open is None:
        return None
    if not any(series == _SERIES for series, _ in run.counters_close):
        return None
    grown = {route: stats.counter_delta(
        run.counters_open, run.counters_close, _SERIES, route=route)
        for route in ("native", "python")}
    read_ = sum(grown.values())
    if read_ <= 0:
        return None
    return 100.0 * grown["native"] / read_
