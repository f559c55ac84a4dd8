"""Of the bytes that entered a new pack of the serve plane while the
window's builds published their layers (``serve/recipe.py``, the
``recipepub-*`` thread a committed layer starts), the share the
publication took from ``index_layer``'s own pass over the layer's
stream, sliced and verified there, and did not read back from the chunk
store: growth of ``makisu_serve_pack_source_bytes_total{source="pass"}``
over the growth of ``pass`` + ``store`` (a chunk the pass found stored
already that is in no pack yet: opened and read at its place in the
order). Near 100 wherever chunks are new. ``None`` where no new pack
was made, and from a program without the series (it reads every novel
chunk back)."""
from pbharness import stats

_SERIES = "makisu_serve_pack_source_bytes_total"


def read(run):
    if run.counters_open is None:
        return None
    if not any(series == _SERIES for series, _ in run.counters_close):
        return None
    grown = {source: stats.counter_delta(
        run.counters_open, run.counters_close, _SERIES, source=source)
        for source in ("pass", "store")}
    packed = sum(grown.values())
    if packed <= 0:
        return None
    return 100.0 * grown["pass"] / packed
