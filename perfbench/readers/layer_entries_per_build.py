"""Entries (files, directories, symlinks, whiteouts) a counted build
committed into layer tars (``makisu_layer_entries_total``, every
kind)."""
from pbharness import hostspans


def read(run):
    return hostspans.counter_per_build(run, "makisu_layer_entries_total")
