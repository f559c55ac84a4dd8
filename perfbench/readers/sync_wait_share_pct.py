"""Sampler frames under ``MemFS._sync`` (``os.sync`` and the wait for
tar's one-second mtime granularity) over the samples of building
threads."""
from pbharness import sampler


def read(run):
    if run.samples is None:
        return None
    return sampler.share_under(run.samples,
                               lambda label: label == "_sync (memfs.py)")
