"""Median, over the builds counted in the window, of one build's wall
seconds from ``client.build()`` called to its terminal record."""
from pbharness import stats


def read(run):
    times = [b.seconds for b in run.counted if b.ok]
    return stats.percentile(times, 50) if times else None
