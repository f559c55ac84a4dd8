"""The plain reference of ``cdc.py`` for streams of hundreds of
megabytes: the same table, recurrence, 13 bits and 2 KiB / 64 KiB
policy, with the candidates computed slab by slab.

``cdc.candidates`` keeps a ``uint32`` copy of the whole stream and
gathers one element of every 4,096-byte segment a step: 8 bytes a
stream byte and a cache miss an element, 2.1 GiB for a 268 MB layer
tar. Here the stream is taken in slabs of 16 MiB. A slab's
bytes are laid out transposed, one row a step and one column a segment,
each column with its 32-byte run-in, so that a step reads one
contiguous row and looks its gear values up in the 1 KiB table; what is
resident is three slabs' worth (the bytes, the transposed copy, the
hits), whatever the stream's length.

Imports nothing of makisu_tpu. Everything but ``candidates`` and
``cut_points`` is the sibling ``cdc.py``'s, loaded by its path."""

from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "perfbench_cdc", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "cdc.py"))
_cdc = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cdc)

AVG_BITS, MIN_SIZE, MAX_SIZE = _cdc.AVG_BITS, _cdc.MIN_SIZE, _cdc.MAX_SIZE
REGTYPE = _cdc.REGTYPE
gear_table = _cdc.gear_table
inflate = _cdc.inflate
sha256_hex = _cdc.sha256_hex
file_sha256_hex = _cdc.file_sha256_hex
tar_members = _cdc.tar_members
tree_members = _cdc.tree_members

_WINDOW = 32          # a byte's term is shifted out of 32 bits after 32 steps
_SEGMENT = 4096
_STEPS = _WINDOW + _SEGMENT
SLAB = 16 << 20       # stream bytes a slab; a multiple of _SEGMENT


def candidates(data, avg_bits: int = AVG_BITS,
               slab: int = SLAB) -> np.ndarray:
    """Positions i with h_i & mask == 0, by the sequential recurrence,
    equal to ``cdc.candidates(data)`` for every ``slab``.

    Within a slab the segments of 4,096 bytes are stepped through side
    by side, as in ``cdc.candidates``: a segment starts 32 bytes early
    from h = 0, and after 32 steps every term of the unknown history has
    left the 32-bit word. The run-in of a slab's first segment is the
    previous slab's last 32 bytes; at the stream's head there is no
    history, and the run-in's terms are zero."""
    if slab <= 0 or slab % _SEGMENT:
        raise ValueError(f"slab must be a positive multiple of {_SEGMENT}")
    stream = np.frombuffer(data, dtype=np.uint8)
    n = len(stream)
    table = gear_table()
    mask = np.uint32((1 << avg_bits) - 1)
    zero, one = np.uint32(0), np.uint32(1)
    found = []
    for start in range(0, n, slab):
        live = min(slab, n - start)
        segments = -(-live // _SEGMENT)
        # Flat bytes [start - 32, start + segments * 4096), zeros where
        # the stream has none; segment k's run-in and own bytes are the
        # 4,128 from k * 4096 on.
        flat = np.zeros(_WINDOW + segments * _SEGMENT, dtype=np.uint8)
        lead = min(_WINDOW, start)
        flat[_WINDOW - lead:_WINDOW + live] = stream[start - lead:start + live]
        # Transposed eight bytes at a time (a plain byte transpose of
        # a slab costs more than the scan): words[j, k] holds bytes
        # 8j..8j+7 of segment k's 4,128, so step s reads byte s % 8 of
        # every word of row s // 8.
        words = np.ascontiguousarray(np.lib.stride_tricks.as_strided(
            flat.view(np.uint64), shape=(segments, _STEPS // 8),
            strides=(_SEGMENT, 8)).T)
        rows = words.view(np.uint8).reshape(_STEPS // 8, segments, 8)
        h = np.zeros(segments, dtype=np.uint32)
        g = np.zeros(segments, dtype=np.uint32)
        low = np.zeros(segments, dtype=np.uint32)
        hit = np.zeros((_SEGMENT, segments), dtype=bool)
        for step in range(_STEPS):
            table.take(rows[step >> 3, :, step & 7], out=g, mode="wrap")
            if start == 0 and step < _WINDOW:
                g[0] = 0      # the stream's head: h = 0 is the definition
            np.left_shift(h, one, out=h)
            np.add(h, g, out=h)
            if step >= _WINDOW:
                np.bitwise_and(h, mask, out=low)
                np.equal(low, zero, out=hit[step - _WINDOW])
        own, segment = np.nonzero(hit)
        pos = np.sort(segment.astype(np.int64) * _SEGMENT + own)
        found.append(pos[pos < live] + start)
    if not found:
        return np.zeros(0, dtype=np.int64)
    return np.concatenate(found)


def cut_points(data) -> list[int]:
    """End offsets of the chunks of ``data``: ``cdc.cut_points``'s
    policy over this module's candidates."""
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
