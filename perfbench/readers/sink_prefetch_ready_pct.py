"""Of the files the window's builds handed to a native sink's reader
threads (``native/layersink.cpp``: the regular files of up to 8 MiB in
a batch of entries that has two or more), the share whose bytes a reader
had read when the tar writer reached them: growth of
``makisu_sink_prefetch_files_total{result="ready"}`` over the growth of
``ready`` + ``waited`` (the writer waited for the reader). ``streamed``
files (over 8 MiB, a batch with fewer than two such files, the
per-entry path) were never a reader's and are left out: how often the
read-ahead is ahead, where it engages. ``None`` where no file went to a
reader, and from a program without the series."""
from pbharness import stats

_SERIES = "makisu_sink_prefetch_files_total"


def read(run):
    if run.counters_open is None:
        return None
    if not any(series == _SERIES for series, _ in run.counters_close):
        return None
    grown = {result: stats.counter_delta(
        run.counters_open, run.counters_close, _SERIES, result=result)
        for result in ("ready", "waited")}
    handed = sum(grown.values())
    if handed <= 0:
        return None
    return 100.0 * grown["ready"] / handed
