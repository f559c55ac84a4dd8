"""``decompress`` calls a cached layer's inflate took, of the layers the
window's builds folded into their trees from a gzip blob: growth of
``makisu_layer_inflate_reads_total`` (``builder/node.py:_apply_layer``,
one add an apply: ``tario.BlockInflater.reads``, a call a block of
4 MiB inflated or 1 MiB of gzip read) over the growth of
``makisu_layer_replay_total{result="inflate"}``. Each call hands the
interpreter lock back and asks for it again, so among several builds
this is the count of turns an inflate waits for: tens a layer where
``gzip.GzipFile`` under ``tarfile``'s stream mode took thousands of
10 KiB. ``None`` where no layer was inflated in the window, and from a
program without the series."""
from pbharness import stats

_READS = "makisu_layer_inflate_reads_total"
_LAYERS = "makisu_layer_replay_total"


def read(run):
    if run.counters_open is None:
        return None
    if not any(series == _READS for series, _ in run.counters_close):
        return None
    layers = stats.counter_delta(run.counters_open, run.counters_close,
                                 _LAYERS, result="inflate")
    if layers <= 0:
        return None
    return stats.counter_delta(run.counters_open, run.counters_close,
                               _READS) / layers
