"""Of the context directories the window's builds asked their listing
for (``snapshot/walk.py:TreeListing``: the checksum pass, the layer
scan's walk, the session checkpoint's walk), the share answered from
the build's memo with no file-system call: growth of
``makisu_tree_listing_dirs_total{result="replayed"}`` over the growth
of both results (``listed`` = one ``scandir`` and an ``lstat`` a
child). About 66 where a layer is scanned (listed once, replayed
twice), about 50 where a build only checksums and checkpoints. A
program without the listing has no such series."""
from pbharness import stats

_SERIES = "makisu_tree_listing_dirs_total"


def read(run):
    if run.counters_open is None:
        return None
    if not any(series == _SERIES for series, _ in run.counters_close):
        return None
    grown = {result: stats.counter_delta(
        run.counters_open, run.counters_close, _SERIES, result=result)
        for result in ("listed", "replayed")}
    asked = sum(grown.values())
    if asked <= 0:
        return None
    return 100.0 * grown["replayed"] / asked
