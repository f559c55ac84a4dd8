"""Megabytes of layer tar the whole worker committed a second of the
window: growth of ``makisu_bytes_hashed_total{path="layer_sink"}`` (the
tar bytes every build's sink digested, whichever sink ran) over the
window's seconds. Where ``commit_mb_per_s`` divides by one build's
``commit_layer`` seconds, this divides by the wall clock all builds
share: the commit rate of a worker, the number ``BASELINE.json`` asks
for in GB/s a chip. Bytes of a commit under way at either edge of the
window count when its sink finishes. ``None`` where the traced run's
counters are missing, no build was counted or the program has no such
series."""
from pbharness import hostspans


def read(run):
    hashed = hostspans.counter_per_build(
        run, "makisu_bytes_hashed_total", path="layer_sink")
    if hashed is None or run.window_s <= 0:
        return None
    # The helper gives a mean a counted build; the rate is the worker's.
    return hashed * len(run.counted) / 1e6 / run.window_s
