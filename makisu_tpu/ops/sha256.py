"""Lane-parallel SHA-256 in JAX for TPU.

SHA-256 is sequential *within* one message (64-byte blocks chain through the
compression function), so single-stream hashing cannot use an accelerator.
The TPU-native formulation hashes L independent messages ("lanes") in
lock-step: every uint32 of hash state is a vector of shape [L], every round
is an elementwise VPU op over all lanes, and a ``lax.scan`` walks the block
axis with per-lane masking for ragged message lengths.

This is the engine behind chunk fingerprinting: content-defined chunking
(ops/gear.py) turns one long layer-tar stream into thousands of independent
chunks, which hash here in parallel. Reference hot path being replaced:
lib/builder/step/common.go:35-67 (dual sequential SHA-256 on CPU).

Layout choices (TPU-first):
- Lane axis last ([..., L]) so it maps onto VPU lanes; L should be a
  multiple of 1024 (8 sublanes x 128 lanes) for full utilization.
- All arithmetic in uint32; rotations are shift-pairs (no rotate primitive
  needed); adds wrap naturally mod 2^32.
- Static shapes only: capacity is LANE_CAP bytes, per-lane byte lengths are
  data. Padding (0x80 marker + big-endian bit length) is computed with
  vectorized masks, not per-lane control flow.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# FIPS 180-4 round constants and initial state.
_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5,
    0x3956c25b, 0x59f111f1, 0x923f82a4, 0xab1c5ed5,
    0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174,
    0xe49b69c1, 0xefbe4786, 0x0fc19dc6, 0x240ca1cc,
    0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7,
    0xc6e00bf3, 0xd5a79147, 0x06ca6351, 0x14292967,
    0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85,
    0xa2bfe8a1, 0xa81a664b, 0xc24b8b70, 0xc76c51a3,
    0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5,
    0x391c0cb3, 0x4ed8aa4a, 0x5b9cca4f, 0x682e6ff3,
    0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2,
], dtype=np.uint32)

_H0 = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19,
], dtype=np.uint32)


def _rotr(x: jax.Array, n: int) -> jax.Array:
    n = jnp.uint32(n)
    return (x >> n) | (x << (jnp.uint32(32) - n))


def _apply_padding(msg_bytes: jax.Array, idx: jax.Array,
                   lengths: jax.Array, total: jax.Array) -> jax.Array:
    """THE SHA-256 padding formula, shared by the whole-buffer path
    (pad_lanes) and the fused block-scan path (sha256_lanes): mask the
    tail, place the 0x80 marker, write the 8-byte big-endian bit
    length. ``idx`` is each byte's absolute message offset; ``total``
    is each lane's padded byte count (num_blocks*64).

    Lane capacity is < 2^28 bytes so the bit length's high word needs
    only bits 29..31 of the byte length; everything stays in uint32.
    """
    ln = lengths[..., None]
    msg = jnp.where(idx < ln, msg_bytes, jnp.uint8(0))
    msg = jnp.where(idx == ln, jnp.uint8(0x80), msg)
    off = idx - (total[..., None] - 8)  # 0..7 inside the length field
    bitlen_lo = (lengths.astype(jnp.uint32) << jnp.uint32(3))[..., None]
    bitlen_hi = (lengths.astype(jnp.uint32) >> jnp.uint32(29))[..., None]
    shift_lo = (jnp.uint32(7) - off.astype(jnp.uint32)) << jnp.uint32(3)
    shift_hi = (jnp.uint32(3) - off.astype(jnp.uint32)) << jnp.uint32(3)
    len_byte = jnp.where(
        off >= 4,
        (bitlen_lo >> (shift_lo & jnp.uint32(31))) & jnp.uint32(0xFF),
        (bitlen_hi >> (shift_hi & jnp.uint32(31))) & jnp.uint32(0xFF),
    ).astype(jnp.uint8)
    return jnp.where((off >= 0) & (off < 8), len_byte, msg)


def pad_lanes(data: jax.Array, lengths: jax.Array) -> jax.Array:
    """Apply SHA-256 padding to L ragged messages stored in a fixed buffer.

    data:    uint8 [L, CAP] with CAP a multiple of 64; bytes beyond each
             lane's length may be arbitrary (they are masked off here).
    lengths: int32 [L], each <= CAP - 9 so the padding fits in-buffer.

    Returns uint8 [L, CAP] fully padded messages. The number of live blocks
    per lane is ``num_blocks(lengths)``; blocks past that hold garbage and
    are masked during the scan.
    """
    cap = data.shape[-1]
    if cap % 64:
        raise ValueError(f"lane capacity {cap} not a multiple of 64")
    lengths = lengths.astype(jnp.int32)
    idx = jax.lax.broadcasted_iota(jnp.int32, data.shape, data.ndim - 1)
    total = num_blocks(lengths) * 64
    return _apply_padding(data, idx, lengths, total)


def num_blocks(lengths: jax.Array) -> jax.Array:
    """Live 64-byte block count per lane after padding."""
    return (lengths.astype(jnp.int32) + 9 + 63) // 64


def bytes_to_words(msg: jax.Array) -> jax.Array:
    """uint8 [L, NB*64] -> big-endian uint32 words [L, NB, 16]."""
    L, cap = msg.shape
    b = msg.reshape(L, cap // 64, 16, 4).astype(jnp.uint32)
    return (
        (b[..., 0] << jnp.uint32(24))
        | (b[..., 1] << jnp.uint32(16))
        | (b[..., 2] << jnp.uint32(8))
        | b[..., 3]
    )


# The SSA formulation below keeps every word in its own loop-carried
# variable (the schedule window rotates by variable renaming: zero
# copies, no gather, static round indices; a carry that stacked the
# state and shifted the schedule with a concatenate every round cost
# ~256KB of pure relayout copies per round per 4096 lanes) with HLO
# size bounded by peeling rounds 0-15 and scanning 3 groups of 16
# schedule rounds — a 16-round group rotates the window exactly once,
# so the scan carry maps positionally.

# Unroll factors for the two scans, (inner 16-round-group scan, outer
# block scan). 3 and 4 are one v5e's optimum as an earlier round's
# builders swept it (no record of the sweep survives; the figures are
# "not measured" until a ledger row carries them). v5e is the one
# device there is, so every backend that is not the CPU gets them;
# another TPU generation has to be swept, not assumed (chip_smoke.py
# refuses a device_kind it does not know). On the CPU the unrolled body
# (192 inlined rounds per scan step) explodes XLA:CPU compile time, so
# it runs 1 and 1. Chosen at trace time from the process's backend. NOT
# cache identity — digests are identical at any unroll.
_UNROLL_DEVICE = (3, 4)
_UNROLL_CPU = (1, 1)


def _unrolls() -> tuple[int, int]:
    return _UNROLL_CPU if jax.default_backend() == "cpu" else _UNROLL_DEVICE


def _round(a, b, c, d, e, f, g, h, k, wt):
    """One SHA-256 round; returns the renamed (a..h)."""
    s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
    ch = (e & f) ^ (~e & g)
    t1 = h + s1 + ch + k + wt
    s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
    maj = (a & b) ^ (a & c) ^ (b & c)
    return t1 + s0 + maj, a, b, c, d + t1, e, f, g


def _schedule_rounds16(v, W, ks):
    """One 16-round schedule group: rotates the 16-word message window
    exactly once by SSA renaming (W mutated in place) and applies 16
    rounds. ``ks`` is any indexable of 16 uint32 round constants. THE
    shared definition for the XLA scan (_compress) and the Pallas
    kernel (ops/sha256_pallas.py) — the round math must never fork."""
    for r in range(16):
        w15 = W[(r + 1) % 16]
        w2 = W[(r + 14) % 16]
        s0w = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> jnp.uint32(3))
        s1w = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> jnp.uint32(10))
        wt = W[r] + s0w + W[(r + 9) % 16] + s1w
        W[r] = wt
        v = _round(*v, ks[r], wt)
    return v


def _compress(state, w16):
    """One SHA-256 block over all lanes. state: [8, L]; w16: [16, L].

    Rounds 0-15 are peeled (they consume the block words directly);
    rounds 16-63 run as a 3-step ``lax.scan`` of 16 SSA rounds each.
    The message-schedule window is 16 separate loop-carried [L] arrays
    rotated by renaming, so no round anywhere stacks, concatenates,
    gathers, or predicates — pure elementwise VPU work.
    """
    W = [w16[i] for i in range(16)]
    v = tuple(state[i] for i in range(8))
    for t in range(16):
        v = _round(*v, jnp.uint32(int(_K[t])), W[t])

    def sixteen(carry, ks):
        v, W = carry
        W = list(W)
        v = _schedule_rounds16(v, W, ks)
        return (v, tuple(W)), None

    ks = jnp.asarray(_K[16:]).reshape(3, 16)
    (v, _), _ = jax.lax.scan(sixteen, (v, tuple(W)), ks,
                             unroll=_unrolls()[0])
    return state + jnp.stack(v)


def sha256_words(words: jax.Array, n_blocks: jax.Array,
                 init_state: jax.Array | None = None) -> jax.Array:
    """SHA-256 over L lanes of pre-padded big-endian words.

    words:    uint32 [L, NB, 16]
    n_blocks: int32 [L] — live blocks per lane; later blocks are masked.
    init_state: optional uint32 [8, L] chaining state (for streaming).

    Returns uint32 [L, 8] digests (big-endian word order).
    """
    L, NB, _ = words.shape
    if init_state is None:
        state0 = jnp.broadcast_to(jnp.asarray(_H0)[:, None], (8, L))
    else:
        state0 = init_state
    # Block axis leads so scan slices are contiguous [16, L] tiles.
    xs = (jnp.arange(NB, dtype=jnp.int32), jnp.transpose(words, (1, 2, 0)))
    n_blocks = n_blocks.astype(jnp.int32)

    def step(state, x):
        bidx, w16 = x
        new = _compress(state, w16)
        keep = (bidx < n_blocks)[None, :]
        return jnp.where(keep, new, state), None

    state, _ = jax.lax.scan(step, state0, xs)
    return jnp.transpose(state, (1, 0))


@jax.named_scope("chunk_sha")
def sha256_lanes_impl(data: jax.Array, lengths: jax.Array,
                      init_state: jax.Array | None = None) -> jax.Array:
    """End-to-end: ragged uint8 lanes [L, CAP] + lengths [L] -> [L, 8] digests.

    Fused block-scan formulation: padding, byteswap, and the [L,64] ->
    [16,L] tile transpose all happen PER BLOCK inside the scan step, so
    the only full-size HBM traffic is one uint8 read of the lane buffer
    (~2 bytes/byte total). The pad_lanes + bytes_to_words + sha256_words
    composition (kept as the test reference; the sharded path also uses
    this fused impl, with a pcast IV) materializes the whole buffer as
    uint32 words plus a transposed copy — ~13 bytes of traffic per
    input byte."""
    L, cap = data.shape
    if cap % 64:
        raise ValueError(f"lane capacity {cap} not a multiple of 64")
    lengths = lengths.astype(jnp.int32)
    nb = num_blocks(lengths)
    total = nb * 64
    if init_state is None:
        state0 = jnp.broadcast_to(jnp.asarray(_H0)[:, None], (8, L))
    else:
        state0 = init_state  # sharded path passes a pcast IV

    def step(state, b):
        blk = jax.lax.dynamic_slice_in_dim(data, b * 64, 64, axis=1)
        idx = b * 64 + jax.lax.broadcasted_iota(jnp.int32, (L, 64), 1)
        msg = _apply_padding(blk, idx, lengths, total)
        w16 = bytes_to_words(msg)[:, 0]  # [L, 64] is one block: NB=1
        new = _compress(state, jnp.transpose(w16))
        keep = (b < nb)[None, :]
        return jnp.where(keep, new, state), None

    state, _ = jax.lax.scan(step, state0,
                            jnp.arange(cap // 64, dtype=jnp.int32),
                            unroll=_unrolls()[1])
    return jnp.transpose(state)


sha256_lanes = functools.partial(jax.jit, donate_argnums=())(
    sha256_lanes_impl)


def digest_bytes(words: np.ndarray) -> list[bytes]:
    """uint32 [L, 8] digest words -> list of 32-byte digests."""
    return [w.astype(">u4").tobytes() for w in np.asarray(words)]


def digest_hex(words: np.ndarray) -> list[str]:
    return [d.hex() for d in digest_bytes(words)]
