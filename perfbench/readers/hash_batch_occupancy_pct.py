"""Mean lane occupancy of the shared hash service's batches in the
window: growth of ``makisu_hash_batch_occupancy`` sum over count."""
from pbharness import stats


def read(run):
    if run.counters_open is None:
        return None
    count = stats.counter_delta(run.counters_open, run.counters_close,
                                "makisu_hash_batch_occupancy_count")
    if count <= 0:
        return None
    return 100.0 * stats.counter_delta(
        run.counters_open, run.counters_close,
        "makisu_hash_batch_occupancy_sum") / count
