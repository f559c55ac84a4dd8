"""Bytes each kernel has to read and write for one call, from its
shapes, and the table of peaks. The roofline of both kernels is HBM
bandwidth: they are integer VPU programs and the VPU's integer rate is
not a published peak, so the share says how far a kernel is from
streaming its data, not how busy the VPU is."""

from __future__ import annotations

import json
import os

from pbharness import xplane

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PEAKS, encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in the peaks "
                         f"table ({_PEAKS}); add it with its source")
    return table[device_kind]


def sha256_lanes_bytes(blocks: int, lanes: int) -> int:
    """One call of the SHA-256 lane kernel: the message schedule input
    of ``blocks`` 64-byte blocks for each of ``lanes`` lanes (16 words
    of 4 bytes a block), each lane's block count, and 8 words of digest
    a lane."""
    return blocks * 16 * lanes * 4 + lanes * 4 + 8 * lanes * 4


def gear_bitmap_bytes(rows: int, row_bytes: int, halo: int) -> int:
    """One call of the gear kernel: ``rows`` rows of ``row_bytes`` live
    bytes behind ``halo`` bytes of the row before, and one bit of
    candidate bitmap a live byte."""
    return rows * (row_bytes + halo) + rows * row_bytes // 8


def call_bytes(kernel: str, hlo_text: str) -> int:
    """Bytes of one call of ``kernel``, its sizes taken from the
    result and operand shapes the trace names."""
    shapes = xplane.shapes_of(hlo_text)
    if kernel == "sha256_lanes_pallas":
        # u32[8, lanes] = custom-call(u32[blocks, 16, lanes], s32[lanes])
        (_, (_, lanes)), (_, (blocks, _, _)) = shapes[0], shapes[1]
        return sha256_lanes_bytes(blocks, lanes)
    if kernel == "gear_bitmap_flat":
        # u32[rows, row_bytes/32] = custom-call(u8[rows, 32, cols])
        (_, (rows, words)), (_, (_, window, cols)) = shapes[0], shapes[1]
        return gear_bitmap_bytes(rows, words * 32, window * cols - words * 32)
    raise KeyError(kernel)


def hbm_roofline_pct(trace, kernel: str, peaks: dict) -> float | None:
    """The least time the chip's memory could take for the kernel's
    calls in the trace, over the time they took."""
    calls = xplane.kernel_calls(trace, kernel)
    seconds = sum(s for _, _, s in calls)
    if not calls or seconds <= 0:
        return None
    total = sum(call_bytes(kernel, text) * n for text, n, _ in calls)
    return 100.0 * total / peaks["hbm_bytes_per_s"] / seconds
