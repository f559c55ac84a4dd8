"""Sampler frames under the chunk store (``cache/chunks.py``,
``storage/cas.py``) over the samples of building threads."""
from pbharness import sampler


def read(run):
    if run.samples is None:
        return None
    return sampler.share_under(
        run.samples,
        lambda label: label.endswith("(chunks.py)")
        or label.endswith("(cas.py)"))
