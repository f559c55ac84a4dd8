"""Seconds a counted build spent applying cached layers to its tree
(span ``apply_layer``, memo replay and gzip inflate alike; the child
``apply_layer.inflate`` and ``makisu_layer_replay_total`` tell them
apart)."""
from pbharness import hostspans


def read(run):
    return hostspans.span_seconds_per_build(run, "apply_layer")
