"""Pallas TPU kernel for lane-parallel SHA-256 compression.

The XLA path (ops/sha256.py sha256_lanes_impl) is SSA-formulated, but
every block step pays XLA overhead the compression math doesn't need: a
[L,64]->[16,L]
tile transpose, dynamic-slice reads, and masking selects threaded
through the scan carry. This kernel does the block chain as pure
elementwise u32 VPU work on [TILE_L]-lane vectors with the hash state
resident in VMEM across the whole block grid:

- XLA pre-pass (same jit): padding (the shared _apply_padding formula),
  byteswap to big-endian words, ONE transpose to block-major
  [NB, 16, L] so each grid step's 16 schedule words are contiguous
  sublane slices.
- Kernel grid (lane_tiles, NB): the block axis iterates sequentially
  (TPU grid order) revisiting the same output tile, so the chaining
  state never leaves VMEM; rounds 0-63 are fully unrolled Python-side —
  the schedule window is 16 SSA variables rotated by renaming, exactly
  the formulation the XLA path uses (ops/sha256.py _compress).
- Ragged lanes: per-lane live-block counts ship as an i32 input; a
  lane's state stops updating at its block count (vector select), so
  digests are bit-identical to the XLA path for any length mix.

SHA-256 needs no reductions — the one Mosaic feature class the gear
kernel had to design around (gear_pallas.py docstring) — so the whole
kernel is elementwise add/xor/and/not/shift on u32, all natively
supported.

Status: the SHA route a TPU build takes by default (chunker/route.py;
it shares the gear kernel's MAKISU_TPU_PALLAS gate). It ran on a TPU
v5e at both production bucket shapes with every digest equal to
hashlib's (``benchmarks/kernel_check.py``, PR 21); its rate against the
XLA path is not measured. Chunk digests are cache identity, so
production dispatch (``sha256_lanes_checked``) additionally compares
the kernel with hashlib once per process and bucket shape, and a
difference fails the build: it never hands the work to another route.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from makisu_tpu.ops import sha256

TILE_L = 1024  # lanes per grid step: [1024] u32 = one (8,128) vector tile


def _sha_kernel(wt_ref, nb_ref, out_ref) -> None:
    from jax.experimental import pallas as pl

    b = pl.program_id(1)

    @pl.when(b == 0)
    def _():
        # Array constants can't be captured by a pallas kernel; build
        # the IV from scalar constants row by row.
        for i in range(8):
            out_ref[i, :] = jnp.full(
                (out_ref.shape[1],), int(sha256._H0[i]), jnp.uint32)

    state = out_ref[:]                        # [8, TL]
    v = tuple(state[i] for i in range(8))
    W = [wt_ref[0, j, :] for j in range(16)]  # 16 x [TL]
    for t in range(16):
        v = sha256._round(*v, jnp.uint32(int(sha256._K[t])), W[t])
    for g in range(3):                        # rounds 16-63, shared math
        ks = [jnp.uint32(int(sha256._K[16 + 16 * g + r]))
              for r in range(16)]
        v = sha256._schedule_rounds16(v, W, ks)
    new = state + jnp.stack(v)
    keep = (b < nb_ref[:])[None, :]
    out_ref[:] = jnp.where(keep, new, state)


@functools.partial(jax.jit, static_argnames=("interpret",))
@jax.named_scope("chunk_sha")
def sha256_lanes_pallas(data: jax.Array, lengths: jax.Array,
                        interpret: bool = False) -> jax.Array:
    """Ragged uint8 lanes [L, CAP] + lengths [L] -> [L, 8] digests.

    Drop-in for sha256.sha256_lanes (no init_state: the sharded pcast-IV
    path keeps the XLA impl). L is padded to TILE_L internally.
    """
    from jax.experimental import pallas as pl

    L, cap = data.shape
    if cap % 64:
        raise ValueError(f"lane capacity {cap} not a multiple of 64")
    lengths = lengths.astype(jnp.int32)
    tl = min(TILE_L, L) if L % TILE_L else TILE_L
    if L % tl:
        pad = tl - L % tl
        data = jnp.pad(data, ((0, pad), (0, 0)))
        lengths = jnp.pad(lengths, (0, pad))  # nb=1 for len 0; harmless
        Lp = L + pad
    else:
        Lp = L
    nb = sha256.num_blocks(lengths)
    padded = sha256.pad_lanes(data, lengths)
    words = sha256.bytes_to_words(padded)         # [Lp, NB, 16]
    wt = jnp.transpose(words, (1, 2, 0))          # [NB, 16, Lp]
    NB = cap // 64
    state = pl.pallas_call(
        _sha_kernel,
        grid=(Lp // tl, NB),
        in_specs=[
            pl.BlockSpec((1, 16, tl), lambda l, b: (b, 0, l)),
            pl.BlockSpec((tl,), lambda l, b: (l,)),
        ],
        out_specs=pl.BlockSpec((8, tl), lambda l, b: (0, l)),
        out_shape=jax.ShapeDtypeStruct((8, Lp), jnp.uint32),
        interpret=interpret,
        # The custom call keeps this name in a device trace under the
        # named scope (the benchmark's roofline reader keys on it).
        name="sha256_lanes_pallas",
    )(wt, nb)
    return jnp.transpose(state)[:L]


# Per-process parity verdicts, one per distinct (lanes, cap) bucket
# shape: each shape compiles a DIFFERENT kernel program (different grid,
# tile, NB), so a verdict for one shape says nothing about another —
# exactly the shape-dependent-miscompile class the probe exists to
# catch.
_parity_ok: dict[tuple[int, int], bool] = {}


def _require_parity(lanes: int, cap: int) -> None:
    """Compare the kernel once per process PER BUCKET SHAPE with
    hashlib on the live backend before trusting it with production
    digests at that shape; raise on a difference.

    Chunk digests are cache identity (cache/chunks.py): a kernel that
    compiled but produced wrong bytes on some future libtpu would
    silently split identity between TPU and CPU builders. The probe
    runs the exact production shape (its compile is the program the
    first real flush at that shape reuses) over ragged lengths covering
    the padding edges. A kernel the compiler refuses, or a readback
    that times out (ops/backend.py sync discipline), propagates as it
    is."""
    key = (lanes, cap)
    if key not in _parity_ok:
        import hashlib
        import time as _time

        from makisu_tpu.ops import backend as _backend
        from makisu_tpu.utils import events as _events
        from makisu_tpu.utils import metrics as _metrics

        _t0 = _time.monotonic()
        rng = np.random.default_rng(0xEC0 ^ lanes ^ cap)
        data = rng.integers(0, 256, size=(lanes, cap), dtype=np.uint8)
        # SHA-256 padding needs 9 spare bytes to stay in-block; edge
        # lengths clamp to cap - 9 so a small-cap shape can never
        # produce a spurious mismatch (hashlib would hash the clamped
        # slice while the kernel was told the unclamped length).
        lengths = rng.integers(0, cap - 9, size=lanes).astype(np.int32)
        edge = tuple(min(e, cap - 9)
                     for e in (0, 1, 55, 56, 63, 64, 100, cap - 9))
        lengths[:len(edge)] = edge[:lanes]
        got = _backend.sync_bounded(
            sha256_lanes_pallas(data, lengths),
            f"sha256 pallas parity probe {lanes}x{cap}")
        _parity_ok[key] = all(
            got[i].astype(">u4").tobytes()
            == hashlib.sha256(data[i, :lengths[i]].tobytes()).digest()
            for i in range(lanes))
        # The per-shape probe is the kernel's own "first compile +
        # first dispatch": one gauge per bucket shape + a device_probe
        # heartbeat on the event bus (the stream the init phases ride).
        probe_s = _time.monotonic() - _t0
        _metrics.gauge_set("makisu_device_parity_probe_seconds",
                           probe_s, bucket=cap,
                           result="ok" if _parity_ok[key] else "failed")
        _events.emit("device_probe", phase="sha_parity_probe",
                     status="done" if _parity_ok[key] else "error",
                     seconds=round(probe_s, 4), bucket=cap,
                     lanes=lanes)
    if not _parity_ok[key]:
        raise RuntimeError(
            f"pallas sha256 kernel {lanes}x{cap}: digest mismatch vs "
            "hashlib on this backend")


def sha256_lanes_checked(data, lengths):
    """The production dispatch of the kernel: ``sha256_lanes_pallas``
    behind the per-process, per-shape parity probe."""
    _require_parity(*data.shape)
    return sha256_lanes_pallas(data, lengths)
