"""Of the counted builds' service seconds, the share their building
threads were off the CPU: 1 - sum of ``thread_cpu_seconds`` over sum of
``service_seconds`` of the terminal records that carry both. What a
building thread waits for: the interpreter lock among the other
builds' threads, the file system, the device and the hash service, the
sink's ring. ``None`` from a worker whose records lack the field."""


def read(run):
    both = [(float(b.terminal["thread_cpu_seconds"]),
             float(b.terminal["service_seconds"]))
            for b in run.counted
            if b.ok and "thread_cpu_seconds" in b.terminal
            and "service_seconds" in b.terminal]
    served = sum(service for _, service in both)
    if served <= 0:
        return None
    return 100.0 * (1.0 - sum(cpu for cpu, _ in both) / served)
