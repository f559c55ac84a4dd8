"""Mean ``service_seconds`` of the counted builds' terminal records: a
request's seconds in the worker from its admission to the end of
``run_build``, queue wait left out (set-up, the build's root span,
tear-down). ``None`` from a worker whose records lack the field."""


def read(run):
    served = [float(b.terminal["service_seconds"])
              for b in run.counted if b.ok and "service_seconds" in b.terminal]
    return sum(served) / len(served) if served else None
