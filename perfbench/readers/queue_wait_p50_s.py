"""Median ``queue_wait_seconds`` of the counted builds' terminal
records: the worker's admission queue."""
from pbharness import stats


def read(run):
    waits = [float(b.terminal.get("queue_wait_seconds", 0.0))
             for b in run.counted if b.ok]
    return stats.percentile(waits, 50) if waits else None
