"""The process's resident high-water mark at the window's close, in
MB: the gauge ``makisu_process_peak_rss_bytes``. A level, not growth
over the window. The worker lives in the harness's process, so what the
generator and the edit hold for a moment (a file's bytes and its edited
copy, up to three files' worth) is in it with what a commit holds; the
check runs after the read and is not."""


def read(run):
    if run.counters_close is None:
        return None
    peak = run.counters_close.get(("makisu_process_peak_rss_bytes", ()))
    if peak is None:
        return None
    return peak / 1e6
