"""The plain reference of ``cdc_slab.py`` for a layer whose blob is
block-parallel gzip (``--gzip-backend pgzip``): the slab reference's
cuts, digests, tar and tree members as they are, and the blob itself
held, byte for byte, to a plain block-by-block deflate of its own tar.

The blob's framing, read off ``native/deflate_common.h`` (the header,
``DeflateSlice``, ``GzipTrailer``) and the slicing in
``native/layersink.cpp`` (``consume``, ``finish_stream``):

- ten header bytes ``1f 8b 08 00``, mtime ``00 00 00 00``, XFL ``00``,
  OS ``ff``: no name, no comment, no extra field, at every level;
- the tar in slices of ``block`` bytes (131,072), each deflated alone
  as a raw stream (window 15 bits, no zlib wrapper, memLevel 8, the
  default strategy: a fresh ``zlib.compressobj(level, DEFLATED, -15)``
  a slice) that ends in ``Z_SYNC_FLUSH``, so it stops at a byte's edge
  with no final block and the slices laid end to end are one deflate
  stream;
- the last slice, ended by ``Z_FINISH``, is **what is left after the
  whole blocks**, 0 to ``block`` - 1 bytes: a tar that is a whole
  number of blocks long ends in an *empty* finished slice (``03 00``),
  and an empty stream is that slice alone. This is the one departure
  from "each slice of the tar, the last by Z_FINISH": the sink hands a
  block on the moment it is full and learns only afterwards that the
  stream ended there (``native/pgzip.cpp``'s one-shot ``pgz_compress``
  finishes the last whole block instead; no layer goes through it);
- eight trailer bytes: CRC-32 of the tar and its length mod 2**32,
  both little-endian.

So the blob is a pure function of the tar, the level and the block
size, and says nothing of how many lanes deflated it: that is the
guarantee ``inflate`` holds the program to. It is also a function of
the zlib that deflated it; the program's sink and this module both use
the host's one ``libz`` (``zlib.ZLIB_RUNTIME_VERSION``).

Imports nothing of makisu_tpu. Everything but ``block_gzip`` and
``inflate`` is the sibling ``cdc_slab.py``'s, loaded by its path."""

from __future__ import annotations

import importlib.util
import os
import zlib

_spec = importlib.util.spec_from_file_location(
    "perfbench_cdc_slab",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "cdc_slab.py"))
_slab = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_slab)

AVG_BITS, MIN_SIZE, MAX_SIZE = _slab.AVG_BITS, _slab.MIN_SIZE, _slab.MAX_SIZE
REGTYPE = _slab.REGTYPE
SLAB = _slab.SLAB
gear_table = _slab.gear_table
candidates = _slab.candidates
cut_points = _slab.cut_points
sha256_hex = _slab.sha256_hex
file_sha256_hex = _slab.file_sha256_hex
tar_members = _slab.tar_members
tree_members = _slab.tree_members

LEVEL = 6
BLOCK = 131072
HEADER = bytes([0x1F, 0x8B, 0x08, 0, 0, 0, 0, 0, 0, 0xFF])


def _pieces(tar, level: int, block: int):
    """The blob piece by piece, each with its name: the header, each
    slice's deflate, the trailer."""
    if block <= 0:
        raise ValueError("block must be positive")
    view = memoryview(tar)
    whole = len(view) // block
    yield "header", HEADER
    for i in range(whole + 1):
        deflater = zlib.compressobj(level, zlib.DEFLATED, -15)
        yield f"block {i}", \
            deflater.compress(view[i * block:(i + 1) * block]) \
            + deflater.flush(zlib.Z_SYNC_FLUSH if i < whole
                             else zlib.Z_FINISH)
    yield "trailer", (zlib.crc32(view) & 0xFFFFFFFF).to_bytes(4, "little") \
        + (len(view) & 0xFFFFFFFF).to_bytes(4, "little")


def block_gzip(tar, level: int = LEVEL, block: int = BLOCK) -> bytes:
    """The gzip member the program has to store for ``tar``."""
    return b"".join(piece for _, piece in _pieces(tar, level, block))


def inflate(path: str, level: int = LEVEL, block: int = BLOCK) -> bytes:
    """The gzip member stored at ``path``, inflated as ``cdc.inflate``
    does; ``ValueError`` where it is not one, and where the stored
    bytes are not ``block_gzip`` of what they inflate to, naming the
    first piece that differs."""
    tar = _slab.inflate(path)
    with open(path, "rb") as f:
        at = 0
        for what, want in _pieces(tar, level, block):
            if f.read(len(want)) != want:
                raise ValueError(
                    f"{path}: not the level-{level} gzip of its own tar in "
                    f"blocks of {block}: {what}, at blob byte {at}, differs")
            at += len(want)
        if f.read(1):
            raise ValueError(f"{path}: bytes after the trailer, at {at}")
    return tar
