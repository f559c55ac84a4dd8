"""Growth of ``makisu_device_h2d_bytes_total`` over the window per
counted build: what the host feed ships to the device, padding
included."""
from pbharness import stats


def read(run):
    if run.counters_open is None or not run.counted:
        return None
    return stats.counter_delta(run.counters_open, run.counters_close,
                               "makisu_device_h2d_bytes_total") \
        / 1e6 / len(run.counted)
