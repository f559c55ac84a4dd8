"""Layers a counted build committed (``makisu_layer_commits_total``,
added once a ``commit_layer``): each costs an ``os.sync()``, a sink's
drain, a chunk-index pass and a cache push whatever its bytes."""
from pbharness import hostspans


def read(run):
    return hostspans.counter_per_build(run, "makisu_layer_commits_total")
