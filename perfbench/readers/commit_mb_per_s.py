"""Megabytes of layer tar a second of layer commit: growth of
``makisu_bytes_hashed_total{path="layer_sink"}`` over the window (the
tar bytes the sinks digested, whichever sink ran) over the summed
``commit_layer`` span seconds of the counted builds. The commit rate
``BASELINE.json`` asks for in GB/s a chip, read end to end on the host:
scan, tar, digests, gzip, device feed and the mtime wait are all in the
span."""
from pbharness import hostspans


def read(run):
    hashed = hostspans.counter_per_build(
        run, "makisu_bytes_hashed_total", path="layer_sink")
    seconds = hostspans.span_seconds_per_build(run, "commit_layer")
    if hashed is None or not seconds:
        return None
    # Both helpers give a mean a build: the counter's over every
    # counted build, the spans' over those that ended well.
    total_bytes = hashed * len(run.counted)
    total_seconds = seconds * sum(1 for b in run.counted if b.ok)
    return total_bytes / 1e6 / total_seconds
