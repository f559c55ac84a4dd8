"""Seconds per counted build that no named operation accounts for: the
self time of the structural spans (the build's root span, ``stage``,
``step``: their duration less what their child spans on the same thread
covered), as the program adds it to
``makisu_span_self_seconds_total{span}`` where each closes. Prints the
three apart on the way. ``None`` from a program without the counter."""
from pbharness import hostspans


def read(run):
    by_span = {name: hostspans.counter_per_build(
        run, "makisu_span_self_seconds_total", span=name)
        for name in hostspans.STRUCTURAL}
    found = {name: s for name, s in by_span.items() if s is not None}
    if not found:
        return None
    print("[perfbench] self seconds a build by structural span: "
          + "  ".join(f"{name} {s:.4f}" for name, s in found.items()),
          flush=True)
    return sum(found.values())
