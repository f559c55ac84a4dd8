"""The cores a layer's gzip stream really used while it had work:
growth of ``makisu_commit_stage_busy_seconds{stage="compress"}`` (busy
seconds summed over the compressor's lanes) over the growth of
``stage="compress_wall"`` (wall seconds in which the stream had a block
queued or deflating). 1.0 for the one zlib stream; up to the pool's
lanes under pgzip, less where the producer cannot keep them fed or the
host gives the pool fewer cores than lanes. ``None`` where no stream
had work, and from a program without ``compress_wall``."""
from pbharness import hostspans


def read(run):
    busy, wall = (hostspans.counter_per_build(
        run, "makisu_commit_stage_busy_seconds", stage=stage)
        for stage in ("compress", "compress_wall"))
    if not busy or not wall:
        return None
    return busy / wall
