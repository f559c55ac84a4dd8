"""Of the chunks the window's builds indexed into the chunk store, the
share whose presence the streamed probe had already found when
``index_layer`` reached them: growth of
``makisu_chunk_exists_prefetch_total{result="hit"}`` over the growth of
all three results (``hit``, ``miss``, ``probe``). A ``probe`` is a chunk
nobody had looked for: its bytes are sliced out of the blob and a writer
stats it inside ``chunk_index``."""
from pbharness import stats

_SERIES = "makisu_chunk_exists_prefetch_total"


def read(run):
    if run.counters_open is None:
        return None
    if not any(series == _SERIES for series, _ in run.counters_close):
        return None
    grown = {result: stats.counter_delta(
        run.counters_open, run.counters_close, _SERIES, result=result)
        for result in ("hit", "miss", "probe")}
    looked_up = sum(grown.values())
    if looked_up <= 0:
        return None
    return 100.0 * grown["hit"] / looked_up
