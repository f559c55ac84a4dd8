"""Wrong-answer switches: each breaks the timed path underneath the
harness, in this process only, so that the self-check and the chip
controls can see ``correct`` come out false. Nothing in makisu_tpu
knows of them; ``run.py --fault NAME`` applies one before the worker
starts. A benchmark run never passes ``--fault``."""

from __future__ import annotations

import os


def cut_mask() -> None:
    """The control: candidates where 12 low bits of the gear hash are
    zero instead of 13. Half the scan's selectivity is the cheaper rule
    a later change could drift to; it breaks the guarantee that a TPU
    builder and a CPU builder cut the same chunks."""
    from makisu_tpu.chunker import hasher
    original = hasher.TPUHasher.__init__

    def init(self, *args, **kwargs):
        original(self, *args, **kwargs)
        self.avg_bits = 12
    hasher.TPUHasher.__init__ = init


def digest_bit() -> None:
    """One bit of one chunk's fingerprint altered where the session
    hands its chunks over."""
    from makisu_tpu.chunker import cdc
    original = cdc.ChunkSession.finish

    def finish(self):
        chunks = original(self)
        if chunks:
            first = chunks[0]
            flipped = bytes([first.digest[0] ^ 1]) + first.digest[1:]
            chunks[0] = cdc.Chunk(first.offset, first.length, flipped)
        return chunks
    cdc.ChunkSession.finish = finish


def stale_tree(run) -> None:
    """After the last build: one byte of one built file changed on
    disk, as if the build had replayed a layer older than the tree."""
    last = max(run.builds, key=lambda b: b.t_done)
    for parent, _, names in sorted(os.walk(last.context)):
        for name in sorted(names):
            if name != "Dockerfile":
                path = os.path.join(parent, name)
                stat = os.stat(path)
                with open(path, "r+b") as f:
                    byte = f.read(1)
                    f.seek(0)
                    f.write(bytes([byte[0] ^ 1]))
                os.utime(path, ns=(stat.st_atime_ns, stat.st_mtime_ns))
                return


BEFORE_WORKER = {"cut_mask": cut_mask, "digest_bit": digest_bit}
AFTER_BUILDS = {"stale_tree": stale_tree}
