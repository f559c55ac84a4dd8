"""Paths in the dirty set a resident session handed to a counted build
(``makisu_session_dirty_paths_total``)."""
from pbharness import hostspans


def read(run):
    return hostspans.counter_per_build(
        run, "makisu_session_dirty_paths_total")
