"""Plain reference for what a build must produce: content-defined cut
points by a sequential gear scan, SHA-256 by hashlib, the layer's tar
members against the tree on disk. Imports nothing of makisu_tpu and
reads nothing the program computed except the outputs under test.

Gear CDC (docs of the program, restated): a 256-entry table G of
splitmix32 values from the seed "maki"; h_i = (h_{i-1} << 1) + G[b_i]
mod 2^32 from h = 0 at the stream's head; byte i is a candidate when
h_i's low 13 bits are zero; a chunk ends after the first candidate at
which it is at least 2 KiB long, or at 64 KiB; the stream's end closes
the last chunk."""

from __future__ import annotations

import hashlib
import io
import os
import tarfile
import zlib

import numpy as np

AVG_BITS = 13
MIN_SIZE = 2 * 1024
MAX_SIZE = 64 * 1024
_WINDOW = 32          # a byte's term is shifted out of 32 bits after 32 steps
_SEGMENT = 4096


def _splitmix32(x: int) -> int:
    x = (x + 0x9E3779B9) & 0xFFFFFFFF
    z = x
    z = ((z ^ (z >> 16)) * 0x21F0AAAD) & 0xFFFFFFFF
    z = ((z ^ (z >> 15)) * 0x735A2D97) & 0xFFFFFFFF
    return (z ^ (z >> 15)) & 0xFFFFFFFF


def gear_table() -> np.ndarray:
    state = 0x6D616B69
    vals = []
    for _ in range(256):
        vals.append(_splitmix32(state))
        state = (state + 0x9E3779B9) & 0xFFFFFFFF
    return np.array(vals, dtype=np.uint32)


def candidates(data: bytes, avg_bits: int = AVG_BITS) -> np.ndarray:
    """Positions i with h_i & mask == 0, by the sequential recurrence.

    The stream is cut into segments of 4096 bytes that are stepped
    through side by side, one byte of each per step. A segment starts
    32 bytes early from h = 0: after 32 steps every term of the
    unknown history has left the 32-bit word, so h is exact from the
    segment's first own byte on. The first segment starts at the
    stream's head, where h = 0 is the definition."""
    n = len(data)
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    g = gear_table()[np.frombuffer(data, dtype=np.uint8)]
    segments = (n + _SEGMENT - 1) // _SEGMENT
    padded = np.zeros(_WINDOW + segments * _SEGMENT, dtype=np.uint32)
    padded[_WINDOW:_WINDOW + n] = g
    starts = np.arange(segments) * _SEGMENT
    h = np.zeros(segments, dtype=np.uint32)
    mask = np.uint32((1 << avg_bits) - 1)
    hit = np.zeros((segments, _SEGMENT), dtype=bool)
    for step in range(_WINDOW + _SEGMENT):
        h = (h << np.uint32(1)) + padded[starts + step]
        if step >= _WINDOW:
            hit[:, step - _WINDOW] = (h & mask) == 0
    return np.nonzero(hit.reshape(-1)[:n])[0]


def cut_points(data: bytes) -> list[int]:
    """End offsets of the chunks of ``data``."""
    cuts = []
    prev = 0
    n = len(data)
    for pos in candidates(data).tolist():
        end = pos + 1
        while end - prev > MAX_SIZE:
            prev += MAX_SIZE
            cuts.append(prev)
        if end - prev >= MIN_SIZE:
            cuts.append(end)
            prev = end
    while n - prev > MAX_SIZE:
        prev += MAX_SIZE
        cuts.append(prev)
    if n > prev:
        cuts.append(n)
    return cuts


REGTYPE = tarfile.REGTYPE


def inflate(path: str) -> bytes:
    """The gzip member stored at ``path``, inflated; ValueError where
    it is not one."""
    try:
        return _inflate(path)
    except zlib.error as e:
        raise ValueError(f"{path}: {e}") from e


def _inflate(path: str) -> bytes:
    inflater = zlib.decompressobj(wbits=31)
    parts = []
    with open(path, "rb") as f:
        while True:
            block = f.read(8 << 20)
            if not block:
                break
            parts.append(inflater.decompress(block))
    parts.append(inflater.flush())
    return b"".join(parts)


def sha256_hex(data) -> str:
    return hashlib.sha256(data).hexdigest()


def file_sha256_hex(path: str) -> str:
    digest = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            block = f.read(8 << 20)
            if not block:
                break
            digest.update(block)
    return digest.hexdigest()


def tar_members(tar: bytes) -> dict:
    """{name: (type, size, mode, mtime, sha256 of content)} of a layer
    tar's members."""
    out = {}
    with tarfile.open(fileobj=io.BytesIO(tar), mode="r:") as tf:
        for member in tf:
            body = b""
            if member.isreg():
                body = tf.extractfile(member).read()
            out[member.name.strip("/")] = (
                member.type, member.size if member.isreg() else 0,
                member.mode & 0o7777, int(member.mtime), sha256_hex(body))
    return out


def tree_members(root: str, sub: str, dest: str) -> dict:
    """What ``COPY sub/ dest/`` of the tree on disk has to put into a
    layer: the same shape as :func:`tar_members`, for regular files."""
    out = {}
    base = os.path.join(root, sub)
    for parent, _, names in os.walk(base):
        for name in names:
            path = os.path.join(parent, name)
            st = os.lstat(path)
            rel = os.path.relpath(path, base)
            out[os.path.join(dest.strip("/"), rel)] = (
                tarfile.REGTYPE, st.st_size, st.st_mode & 0o7777,
                int(st.st_mtime), file_sha256_hex(path))
    return out
