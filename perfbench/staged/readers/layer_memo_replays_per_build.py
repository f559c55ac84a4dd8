"""Cached layers a counted build folded into its tree from the
session's memo, with no blob opened and no inflate
(``makisu_layer_replay_total{result="memo"}``,
``builder/node.py:_apply_layer``); ``result="inflate"`` is
``apply_inflate_reads_per_layer``'s. A worker that never replayed a
memo exports no such series: where it replayed, inflated or left unread
any cached layer (the counter is there under another ``result``) that
is 0.0, and ``None`` only from a run without the counter."""
from pbharness import hostspans

_SERIES = "makisu_layer_replay_total"


def read(run):
    memo = hostspans.counter_per_build(run, _SERIES, result="memo")
    if memo is None and hostspans.counter_per_build(run, _SERIES) is not None:
        return 0.0
    return memo
