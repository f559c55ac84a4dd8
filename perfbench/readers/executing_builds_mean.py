"""Builds executing at once, as a mean over the window: the counted
builds' ``service_seconds`` (admission to the end of ``run_build``,
queue wait left out) summed, over the window's seconds. About the
worker's slots where it has an admission limit and a queue behind it,
about the number of clients where it has none. A build in flight at
either edge of the window counts whole or not at all, as the window
counts it. ``None`` from a worker whose records lack the field."""


def read(run):
    served = [float(b.terminal["service_seconds"])
              for b in run.counted if b.ok and "service_seconds" in b.terminal]
    if not served or run.window_s <= 0:
        return None
    return sum(served) / run.window_s
