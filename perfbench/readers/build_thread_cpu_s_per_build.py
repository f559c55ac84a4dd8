"""Mean ``thread_cpu_seconds`` of the counted builds' terminal records:
the building thread's own CPU seconds (``time.thread_time()``) from a
request's admission to the end of ``run_build``, the interval of
``service_seconds``. Not in it: the threads that work beside the
builder (the native sink's compressor, the chunk store's pools, the
hash service's dispatcher). ``None`` from a worker whose records lack
the field."""


def read(run):
    burned = [float(b.terminal["thread_cpu_seconds"])
              for b in run.counted
              if b.ok and "thread_cpu_seconds" in b.terminal]
    return sum(burned) / len(burned) if burned else None
