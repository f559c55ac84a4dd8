"""Share of the traced window in which no operation ran on the device."""


def read(run):
    trace = run.device_trace
    if trace is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
