"""Layer commits under way at once, as a mean over the window: the
counted builds' ``commit_layer`` span seconds (``steps/base.py``: scan,
tar, digests, gzip, device feed, the sink's drain) summed, over the
window's seconds. Beside ``executing_builds_mean``: of the builds
executing, how many were inside a commit. A build in flight at either
edge of the window counts whole or not at all, as the window counts
it. ``None`` where no counted build's record holds such a span."""


def read(run):
    seconds = [float(d or 0.0) for b in run.counted if b.ok
               for name, d in b.spans if name == "commit_layer"]
    if not seconds or run.window_s <= 0:
        return None
    return sum(seconds) / run.window_s
