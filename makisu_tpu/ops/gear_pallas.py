"""Pallas TPU kernel for the fused Gear scan.

The XLA path (ops/gear.py) materializes the uint32 hash array between the
log-doubling steps; this kernel keeps everything — splitmix table values,
the 5 shifted-add steps, the mask compare, and the bit-pack — inside one
VMEM-resident kernel, writing only the packed bitmap (3% of input bytes)
back to HBM.

Formulation (sublane-major): the stream is restaged into rows of ROW
live bytes with a HALO-byte left halo, and each row is laid out
COLUMN-major as a [32, (HALO+ROW)/32] tile: byte j of the row sits at
[j % 32, j // 32]. Because the column height equals the Gear window
(32), the rolling hash FACTORS per column:

    h[s, c] = P[s, c] + Q[c-1] * 2^(s+1)          (mod 2^32)

where P[s, c] = sum_{s'<=s} G(b[s', c]) << (s - s') is a weighted
prefix scan that never leaves its column, and Q[c] = P[31, c] is the
column total. The 2^(s+1) factor kills every contribution older than
32 positions (shifts >= 32 vanish mod 2^32), so the single lane-shifted
borrow term carries exactly the window tail from the previous column —
no cross-column concatenation anywhere. P is computed by the shared
log-doubling recurrence (gear._windowed_sum) with a pure sublane shift.

The layout choices are all Mosaic-driven (the compiler's words for a
v5e target):
- Reductions happen on int32 bitcasts ("Reductions over unsigned
  integers not implemented").
- The 32-position bit-pack reduces over the SUBLANE axis of an int32
  weighted mask; the first formulation's lane-split reshape
  ([T, 8192] -> [T, 256, 32]) was rejected ("unsupported shape cast"
  on the i1 vector).
- An earlier sublane-rotate-with-lane-borrow shift was rejected at the
  sublane concat ("result/input offset mismatch on non-concat
  dimension" — the wrapped operand carries a lane offset from its
  pad); the per-column factorization above removes the concat
  entirely.

The zero-filled halo at the stream head makes positions < 31 differ from
true zero-history hashes, but those sit far below the minimum chunk size
and can never become cuts, so selected chunks are identical (asserted in
tests against the XLA path).

Status: the kernel (``gear_bitmap_flat``) is the gear route a TPU
build takes by default (chunker/route.py); MAKISU_TPU_PALLAS=0/1
forces. It ran on a TPU v5e at the production shape (one 4 MiB block,
with and without a halo prefix) bit-identical to ``gear.gear_hash_ref``
(``benchmarks/kernel_check.py``, PR 21). Its rate against the XLA path
is not measured. v5e is the one device the kernel has met: the tile
sizes below are that generation's.
"""

from __future__ import annotations

import functools
import os
import typing

import jax
import jax.numpy as jnp
import numpy as np

from makisu_tpu.ops import gear

HALO = 128            # row overlap; must be >= gear.WINDOW and % 32 == 0
ROW = 8192            # live bytes per row
ROW_TILE = 8          # rows per grid step
_HCOLS = HALO // 32   # halo columns in the sublane-major tile
_CCOLS = ROW // 32    # live columns (= packed words per row)


def env_enabled(platform: str | None = None,
                environ: typing.Mapping[str, str] = os.environ) -> bool:
    """Whether a process on ``platform`` (default: this process's JAX
    backend) rides the Pallas kernels: yes on TPU backends, no
    elsewhere (interpret mode exists for tests, not production);
    MAKISU_TPU_PALLAS=1/0 forces both kernels either way. The one
    statement of the rule: chunker/route.py asks it too."""
    forced = environ.get("MAKISU_TPU_PALLAS", "")
    if forced in ("0", "1"):
        return forced == "1"
    if platform is None:
        platform = jax.default_backend()
    return platform == "tpu"


def nrows_for(live: int) -> int:
    """Live row count for a ``live``-byte region — the one rounding rule
    shared by the kernel wrappers and the bitmap-slicing callers."""
    return max((live + ROW - 1) // ROW, 1)


def padded_rows_for(live: int) -> int:
    """``nrows_for`` rounded up to the kernel's grid tile."""
    return ((nrows_for(live) + ROW_TILE - 1) // ROW_TILE) * ROW_TILE


def quantize_flat(buf: np.ndarray, start: int, live: int) -> np.ndarray:
    """Host-side input staging for ``gear_bitmap_flat``: zero-pad the
    live region to the row grid. Returns ``buf`` itself when already
    aligned (the steady-state 4MiB block path pays no copy)."""
    need = padded_rows_for(live) * ROW
    if len(buf) == start + need:
        return buf
    qbuf = np.zeros(start + need, dtype=np.uint8)
    qbuf[:len(buf)] = buf
    return qbuf


def stage_rows(buf: np.ndarray, start: int, n: int) -> tuple[np.ndarray, int]:
    """Restage ``buf[start:start+n]`` into sublane-major halo rows.

    Returns (rows, nrows): rows is uint8 [R, 32, _HCOLS+_CCOLS] with R
    = nrows rounded UP to a multiple of ROW_TILE (trailing rows all
    zero); nrows is the LIVE row count — callers slice the kernel's
    bitmap to ``words[:nrows]``. Byte j of row r (j counts from the
    halo start) sits at ``rows[r, j % 32, j // 32]``. Positions beyond
    ``n`` are zero-filled (callers mask the bitmap tail).
    """
    nrows = nrows_for(n)
    nrows_padded = padded_rows_for(n)
    flat = np.zeros((nrows_padded, HALO + ROW), dtype=np.uint8)
    for r in range(nrows):
        lo = start + r * ROW - HALO
        hi = min(start + r * ROW + ROW, start + n)
        dst_off = 0
        if lo < 0:
            dst_off = -lo
            lo = 0
        seg = buf[lo:hi]
        flat[r, dst_off:dst_off + len(seg)] = seg
    # Column-major within each row: [R, COLS, 32] -> [R, 32, COLS].
    cols = _HCOLS + _CCOLS
    return np.ascontiguousarray(
        flat.reshape(nrows_padded, cols, 32).transpose(0, 2, 1)), nrows


def _shift_sublane(h: jax.Array, m: int) -> jax.Array:
    """Sublane-only shift down by m with zero fill (no column borrow)."""
    return jnp.pad(h[:, :32 - m, :], ((0, 0), (m, 0), (0, 0)))


def _gear_kernel(avg_bits: int, rows_ref, out_ref) -> None:
    d = rows_ref[:]                           # [T, 32, COLS] uint8
    # The recurrence itself is gear._windowed_sum — the ONE
    # cache-identity-bearing definition — run per column with a pure
    # sublane shift; the cross-column window tail is the Q-borrow term
    # (see module docstring).
    p = gear._windowed_sum(gear._gear_value(d), shift=_shift_sublane)
    s_iota = jax.lax.broadcasted_iota(jnp.uint32, (1, 32, 1), 1)
    q = jax.lax.bitcast_convert_type(
        jnp.sum(jnp.where(s_iota == 31,
                          jax.lax.bitcast_convert_type(p, jnp.int32), 0),
                axis=1, keepdims=True, dtype=jnp.int32),
        jnp.uint32)                           # [T, 1, COLS] column totals
    q_prev = jnp.pad(q, ((0, 0), (0, 0), (1, 0)))[:, :, :-1]
    # 2 << s == 2^(s+1); s == 31 wraps to 0, dropping out-of-window terms.
    h = p + q_prev * (jnp.uint32(2) << s_iota)
    live = h[:, :, _HCOLS:]                   # [T, 32, _CCOLS]
    mask = (live & jnp.uint32((1 << avg_bits) - 1)) == 0
    # Bit-pack via an int32 SUBLANE reduction (see module docstring):
    # word c's bit s is position c*32+s; two's-complement wrap makes the
    # int32 weighted sum bit-identical to the uint32 one.
    weights = jnp.int32(1) << jax.lax.broadcasted_iota(
        jnp.int32, (1, 32, 1), 1)
    packed = jnp.sum(mask.astype(jnp.int32) * weights, axis=1,
                     dtype=jnp.int32)         # [T, _CCOLS]
    out_ref[:] = jax.lax.bitcast_convert_type(packed, jnp.uint32)


def _invoke_kernel(rows: jax.Array, avg_bits: int,
                   interpret: bool, name: str) -> jax.Array:
    """The one pallas_call site: uint8 rows [R, 32, COLS] (R a multiple
    of ROW_TILE) → packed candidate bitmap [R, ROW//32]. ``name`` is
    the calling entry point's: XLA names the custom call after the
    innermost scope, and the benchmark finds the kernel in a device
    trace as ``%gear_bitmap_flat.N``, inside or outside a
    ``named_scope``."""
    from jax.experimental import pallas as pl

    R = rows.shape[0]
    if R % ROW_TILE or rows.shape[1:] != (32, _HCOLS + _CCOLS):
        raise ValueError(f"bad row staging shape {rows.shape}")
    kernel = functools.partial(_gear_kernel, avg_bits)
    return pl.pallas_call(
        kernel,
        grid=(R // ROW_TILE,),
        in_specs=[pl.BlockSpec((ROW_TILE, 32, _HCOLS + _CCOLS),
                               lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((ROW_TILE, _CCOLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, _CCOLS), jnp.uint32),
        interpret=interpret,
        name=name,
    )(rows)


@functools.partial(jax.jit, static_argnames=("avg_bits", "interpret"))
@jax.named_scope("gear_scan")
def gear_bitmap_rows(rows: jax.Array,
                     avg_bits: int = gear.DEFAULT_AVG_BITS,
                     interpret: bool = False) -> jax.Array:
    """uint8 rows [R, 32, COLS] → packed candidate bitmap [R, ROW//32]."""
    return _invoke_kernel(rows, avg_bits, interpret, "gear_bitmap_rows")


@functools.partial(jax.jit,
                   static_argnames=("start", "avg_bits", "interpret"))
@jax.named_scope("gear_scan")
def gear_bitmap_flat(buf: jax.Array, start: int,
                     avg_bits: int = gear.DEFAULT_AVG_BITS,
                     interpret: bool = False) -> jax.Array:
    """Fused restage + kernel for a flat stream block.

    ``buf`` is uint8 [start + R*ROW] with R a multiple of ROW_TILE: up
    to ``start`` bytes of true halo history, then the live region
    zero-padded to the row grid (``padded_rows_for(live) * ROW`` —
    callers quantize host-side so distinct tail sizes share compiles at
    64 KiB granularity instead of retracing per byte count). The row
    restaging (pad → overlap-window → sublane-major transpose) runs as
    XLA ops ON DEVICE in the same program as the kernel — the host
    ships the flat bytes once and reads back only the packed bitmap.
    (The numpy ``stage_rows`` restage costs host memcpys comparable to
    the whole kernel runtime at 80+ GB/s; this path exists so the
    production chunker never pays them.)

    Returns packed words [R, ROW//32]; rows past ``nrows_for(live)``
    and bit positions past ``live`` are garbage the caller must slice
    off (exactly ``stage_rows``'s contract).
    """
    need = buf.shape[0] - start
    if need % (ROW_TILE * ROW):
        raise ValueError(
            f"live region {need} not quantized to ROW_TILE*ROW "
            f"(use padded_rows_for)")
    R = need // ROW
    lpad = max(HALO - start, 0)
    base = start + lpad - HALO
    seg = jnp.pad(buf, (lpad, 0))[base:base + HALO + need]
    live_m = seg[HALO:].reshape(R, ROW)
    halos = jnp.concatenate(
        [seg[:HALO][None, :], live_m[:-1, ROW - HALO:]], axis=0)
    rows = (jnp.concatenate([halos, live_m], axis=1)
            .reshape(R, _HCOLS + _CCOLS, 32).transpose(0, 2, 1))
    return _invoke_kernel(rows, avg_bits, interpret, "gear_bitmap_flat")


@functools.partial(jax.jit, static_argnames=("avg_bits", "interpret"))
@jax.named_scope("gear_scan")
def gear_bitmap_batch(blocks: jax.Array,
                      avg_bits: int = gear.DEFAULT_AVG_BITS,
                      interpret: bool = False) -> jax.Array:
    """Batched kernel route for [B, N] stream blocks (N a multiple of
    ROW_TILE*ROW), zero history per stream — the SnapshotHasher shape.

    Returns packed words [B, N//32]. NOTE: positions < WINDOW differ
    from gear.gear_bitmap's zero-G-value head (the kernel's halo is
    zero BYTES, G(0) != 0); both sit far below the minimum chunk size
    and never become cuts — same caveat as every kernel path.
    """
    B, n = blocks.shape
    if n % (ROW_TILE * ROW):
        raise ValueError(f"block bytes {n} not a multiple of "
                         f"{ROW_TILE * ROW}")
    R = n // ROW
    live_m = blocks.reshape(B, R, ROW)
    halos = jnp.pad(live_m[:, :-1, ROW - HALO:],
                    ((0, 0), (1, 0), (0, 0)))   # stream head: zero halo
    rows = (jnp.concatenate([halos, live_m], axis=2)
            .reshape(B * R, _HCOLS + _CCOLS, 32).transpose(0, 2, 1))
    words = _invoke_kernel(rows, avg_bits, interpret, "gear_bitmap_batch")
    return words.reshape(B, R * _CCOLS)


def gear_candidates(buf: np.ndarray, start: int, n: int,
                    avg_bits: int = gear.DEFAULT_AVG_BITS,
                    interpret: bool | None = None) -> np.ndarray:
    """Candidate cut positions (relative to ``start``) for
    ``buf[start:start+n]`` via the Pallas kernel."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    rows, nrows = stage_rows(buf, start, n)
    words = np.asarray(gear_bitmap_rows(rows, avg_bits, interpret))
    bits = gear.unpack_bits_np(words[:nrows], nrows * ROW)
    flat = bits.reshape(-1)[:n]
    return np.nonzero(flat)[0]
