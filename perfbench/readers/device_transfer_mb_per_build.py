"""Megabytes that crossed between host and device per counted build,
both directions, gear scan and SHA lanes
(``makisu_device_transfer_bytes_total``, every label)."""
from pbharness import hostspans


def read(run):
    moved = hostspans.counter_per_build(
        run, "makisu_device_transfer_bytes_total")
    return None if moved is None else moved / 1e6
