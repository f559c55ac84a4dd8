"""Builds a batch of the shared hash service carried, as a mean over
the batches dispatched in the window: growth of
``makisu_hash_batch_owners`` sum over count (``chunker/service.py``:
the submitting sessions in a batch, observed where the cross-build
counter is added). 1.0 where every build rode alone;
``hash_cross_build_batch_pct`` says how many batches mixed builds, this
how many builds they mixed. ``None`` where no batch was dispatched, the
traced run's counters are missing or the program has no such
histogram."""
from pbharness import stats


def read(run):
    if run.counters_open is None:
        return None
    count = stats.counter_delta(run.counters_open, run.counters_close,
                                "makisu_hash_batch_owners_count")
    if count <= 0:
        return None
    return stats.counter_delta(
        run.counters_open, run.counters_close,
        "makisu_hash_batch_owners_sum") / count
