"""90th percentile of the counted builds' wall seconds. Meant for
windows that count 100 builds or more (ten samples beyond it); the
count is on the run's ``counted`` line."""
from pbharness import stats


def read(run):
    times = [b.seconds for b in run.counted if b.ok]
    return stats.percentile(times, 90) if times else None
