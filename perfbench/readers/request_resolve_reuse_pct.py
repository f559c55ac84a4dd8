"""Of the questions the window's requests put about themselves more
than once (their ``argv`` as flags; --root, --storage, the context and
``<storage>/chunks`` as real paths), the share the record made at the
request's admission answered (``worker/server.py:_effective_flags``,
``utils/pathutils.py:real_path``): growth of
``makisu_request_resolve_total{result="reused"}`` over the growth of
``reused`` + ``done`` (a parser ran, a path was walked through its
symlinks), both kinds together. ``None`` where nothing was asked, and
from a program without the series."""
from pbharness import stats

_SERIES = "makisu_request_resolve_total"


def read(run):
    if run.counters_open is None:
        return None
    if not any(series == _SERIES for series, _ in run.counters_close):
        return None
    grown = {result: stats.counter_delta(
        run.counters_open, run.counters_close, _SERIES, result=result)
        for result in ("reused", "done")}
    asked = sum(grown.values())
    if asked <= 0:
        return None
    return 100.0 * grown["reused"] / asked
