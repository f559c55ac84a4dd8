"""Of the counted builds' ``service_seconds``, the share the thread-state
reader sampled: growth of ``makisu_thread_state_seconds_total``, every
state and span, ÷ the sum of ``service_seconds`` of the counted builds'
terminal records. Near 100 where the reader saw the builds it is
divided by: a check on the other thread-state metrics, not a target.
Under it: a request's set-up and tear-down (outside every span), the
beats before a thread is watched; over it: threads beside the builder
that open spans of their own (``recipe_publish``), builds in the window
that were not counted. Prints on the way the table the thread-state
metrics are cut from: seconds a counted build by span and state, the
ten largest spans, with the scheduler's exact ``run`` and ``runqueue``
beside the six sampled states. ``None`` where there is no reader."""
from pbharness import threadstates


def _reader_line() -> str:
    """The native reader's own cost, from the worker in this process;
    nothing from a program without it."""
    try:
        from makisu_tpu.utils import resources
        vitals = resources.thread_state_vitals()
    except (ImportError, AttributeError):
        return ""
    if not vitals:
        return ""
    beats = max(vitals["beats"], 1)
    return (f"; reader: lock_ref {int(vitals['lock_ref']):#x} from "
            f"{vitals['sightings']:.0f} sightings, {vitals['beats']:.0f} "
            f"beats, {vitals['reads'] / beats:.1f} reads and "
            f"{1e6 * vitals['busy_seconds'] / beats:.0f} us a beat")


def read(run):
    rows = threadstates.by_span(run)
    served = sum(float(b.terminal["service_seconds"]) for b in run.counted
                 if b.ok and "service_seconds" in b.terminal)
    if not rows or served <= 0:
        return None
    print("[perfbench] thread seconds a counted build by innermost span and "
          f"state (source {threadstates.source(run):.0f}{_reader_line()}):\n"
          + "\n".join("[perfbench]   " + line
                      for line in threadstates.table_lines(rows)),
          flush=True)
    sampled = sum(row.get(state, 0.0) for row in rows.values()
                  for state in threadstates.STATES)
    return 100.0 * sampled * len(run.counted) / served
