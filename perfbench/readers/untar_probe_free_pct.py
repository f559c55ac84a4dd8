"""Of the members the window's builds unpacked under ``--root`` from
cached layers (``snapshot/memfs.py:MemFS._untar_one``, under
``apply_layer{untar}``), the share made by a first write that
succeeded, with no question put to the file system before it: growth of
``makisu_untar_members_total{result="created"}`` over the growth of
``created`` + ``probed`` (something was in the member's place and was
compared, kept or replaced; a hard link; a whiteout). Near 100 under
an empty root, where only the directories a second layer states again
collide. ``None`` where nothing was unpacked on disk, and from a
program without the series."""
from pbharness import stats

_SERIES = "makisu_untar_members_total"


def read(run):
    if run.counters_open is None:
        return None
    if not any(series == _SERIES for series, _ in run.counters_close):
        return None
    grown = {result: stats.counter_delta(
        run.counters_open, run.counters_close, _SERIES, result=result)
        for result in ("created", "probed")}
    unpacked = sum(grown.values())
    if unpacked <= 0:
        return None
    return 100.0 * grown["created"] / unpacked
