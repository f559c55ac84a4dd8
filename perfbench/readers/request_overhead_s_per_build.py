"""Mean ``setup_seconds`` + ``teardown_seconds`` of the counted builds'
terminal records: the part of a request's service outside its build's
root span (``run_build`` around ``cli.main``, and ``cli.main`` around
the span: flags parsed, sinks and registry bound, report and history,
the build retired, the eviction check). ``None`` from a worker whose
records lack the fields."""


def read(run):
    outside = [float(b.terminal["setup_seconds"])
               + float(b.terminal["teardown_seconds"])
               for b in run.counted if b.ok and "setup_seconds" in b.terminal
               and "teardown_seconds" in b.terminal]
    return sum(outside) / len(outside) if outside else None
