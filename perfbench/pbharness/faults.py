"""Wrong-answer switches: each breaks the timed path underneath the
harness, in this process only, so that the self-check and the chip
controls can see ``correct`` come out false. Nothing in makisu_tpu
knows of them; ``run.py --fault NAME`` applies one before the worker
starts or, of ``AFTER_BUILDS``, after the window and before the check.
``remade_storage`` is the one that must leave ``correct`` true: the
check goes by the lane's record, not by what stands on disk. A
benchmark run never passes ``--fault``."""

from __future__ import annotations

import os
import sys


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


def _told(fault: str, build) -> None:
    print(f"FAULT {fault} {build.tag}", file=sys.stderr, flush=True)


def remade_storage(run) -> None:
    """Every cold build the lane removed has its directory again, with
    the one file the worker's deferred stat-cache save makes there when
    it runs under the removal. The check must draw none of them."""
    removed = [b for b in run.counted if b.ok and not b.kept]
    if not removed:
        raise SystemExit("remade_storage: the lane removed no counted "
                         "build; take another seed")
    for b in removed:
        os.makedirs(b.storage, exist_ok=True)
        with open(os.path.join(b.storage, "content_id_cache.json"), "w",
                  encoding="utf-8") as f:
            f.write("{}")
        _told("remade_storage", b)


def lost_manifest(run) -> None:
    """The last counted build, which the lane kept, has lost its
    manifest."""
    from pbharness import check
    os.unlink(check.manifest_path(run.counted[-1]))
    _told("lost_manifest", run.counted[-1])


def stored_chunk_byte(run) -> None:
    """One byte of one chunk the last counted build recorded is altered
    in its chunk store, through the store's owner."""
    from pbharness import check
    last = run.counted[-1]
    manifest, _, entries = check.Checker(None, {})._manifest(last)
    name = entries[manifest["layers"][0]["digest"]]["chunks"][0][2]
    store = check.chunk_store(last)
    data = store.read(name)
    store.put(name, bytes([data[0] ^ 1]) + data[1:])
    _told("stored_chunk_byte", last)


BEFORE_WORKER = {"cut_mask": cut_mask, "digest_bit": digest_bit}
AFTER_BUILDS = {"stale_tree": stale_tree, "remade_storage": remade_storage,
                "lost_manifest": lost_manifest,
                "stored_chunk_byte": stored_chunk_byte}
