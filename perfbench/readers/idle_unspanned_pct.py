"""Of the device's idle seconds during which a build was executing, the
share that no span below ``step`` accounts for: the innermost program
span open on the building threads was the command's root span,
``stage`` or ``step``. Prints the idle seconds by span on the way."""
from pbharness import driver, hostspans


def read(run):
    trace = run.device_trace
    path = hostspans.trace_path(run) if trace is not None else None
    if path is None:
        return None
    events = hostspans.host_events(path, driver._WINDOW_OPEN_MARK)
    if not events:
        return None
    charged, total = hostspans.charge_gaps(events, trace.gaps)
    print("[perfbench] idle seconds by innermost program span "
          f"({total:.2f}s with a build executing): " + "  ".join(
              f"{name} {seconds:.2f}" for name, seconds in sorted(
                  charged.items(), key=lambda kv: -kv[1])[:14]),
          flush=True)
    return hostspans.unspanned_pct(charged, total)
