"""Of the shared hash service's batches dispatched in the window, the
share that carried chunks of more than one build: growth of
``makisu_hash_cross_build_batches_total`` over growth of
``makisu_hash_batches_total`` (both buckets). ``None`` where no batch
was dispatched or the traced run's counters are missing."""
from pbharness import stats


def read(run):
    if run.counters_open is None:
        return None
    batches = stats.counter_delta(run.counters_open, run.counters_close,
                                  "makisu_hash_batches_total")
    if batches <= 0:
        return None
    return 100.0 * stats.counter_delta(
        run.counters_open, run.counters_close,
        "makisu_hash_cross_build_batches_total") / batches
