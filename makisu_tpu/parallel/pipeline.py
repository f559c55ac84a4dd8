"""Mesh-sharded hashing pipeline: the multi-chip form of ops/gear +
ops/sha256.

The long-stream dimension is genuinely sequence-parallel: Gear's hash at
position i depends on at most the 31 previous bytes (mod 2^32 window), so
a shard only needs a 31-byte (WINDOW-1) halo from its left neighbor —
one ``lax.ppermute`` over ICI per scan, the cheapest possible collective.
This is the project's ring-attention analogue (SURVEY.md §5): where the
reference hashes a layer as one sequential CPU stream
(lib/builder/step/common.go:35-67), here the stream splits across chips
with exact boundary stitching.

Chunk-lane SHA-256 is embarrassingly parallel over lanes; sharding the
lane axis over the whole mesh needs no collectives at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from makisu_tpu.ops import gear, sha256
from makisu_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS


def _gear_bitmap_local(block: jax.Array, axis_name: str,
                       avg_bits: int) -> jax.Array:
    """Per-shard candidate bitmap with a left-neighbor halo over
    ``axis_name``. One evaluation per shard: the neighbor's last 31
    bytes arrive by ppermute, their G-VALUES seed the windowed sum
    (masked to zero on shard 0, whose stream starts cold) — the same
    halo mechanism the blocked scan uses between 64KiB blocks, so each
    shard also gets the bandwidth-lean path when its local size allows.
    """
    n_shards = jax.lax.psum(1, axis_name)
    halo_bytes = jax.lax.ppermute(
        block[..., -(gear.WINDOW - 1):], axis_name,
        perm=[(i, (i + 1) % n_shards) for i in range(n_shards)])
    halo_g = gear._gear_value(halo_bytes)
    # Shard 0 has no left history: zero G-halo == the zero-history
    # start convention (zero-valued halo BYTES would not be: G[0] != 0).
    is_first = jax.lax.axis_index(axis_name) == 0
    halo_g = jnp.where(is_first, jnp.uint32(0), halo_g)
    return gear.gear_bitmap_with_halo(block, halo_g, avg_bits)


def gear_bitmap_sharded(mesh: Mesh, avg_bits: int = gear.DEFAULT_AVG_BITS):
    """Jitted [B, N] uint8 → [B, N//32] uint32 candidate bitmap, with B
    over the data axis and N over the seq axis (halo-stitched)."""

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=P(DATA_AXIS, SEQ_AXIS),
        out_specs=P(DATA_AXIS, SEQ_AXIS))
    def _shard(block):
        return _gear_bitmap_local(block, SEQ_AXIS, avg_bits)

    return jax.jit(_shard)


def sha256_lanes_sharded(mesh: Mesh):
    """Jitted ragged-lane SHA-256 with lanes spread over every device."""
    lanes_spec = P((DATA_AXIS, SEQ_AXIS), None)
    vec_spec = P((DATA_AXIS, SEQ_AXIS))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(lanes_spec, vec_spec),
        out_specs=P((DATA_AXIS, SEQ_AXIS), None))
    def _shard(data, lengths):
        # Fused block-scan path (padding/packing inside the scan step),
        # same as single-chip. The scan carry must be device-varying
        # like the data (shard_map typing); mark the constant IV
        # accordingly.
        state0 = jnp.broadcast_to(jnp.asarray(sha256._H0)[:, None],
                                  (8, data.shape[0]))
        state0 = jax.lax.pcast(state0, (DATA_AXIS, SEQ_AXIS),
                               to="varying")
        return sha256.sha256_lanes_impl(data, lengths, init_state=state0)

    return jax.jit(_shard)


def snapshot_hash_step(mesh: Mesh, avg_bits: int = gear.DEFAULT_AVG_BITS):
    """The full sharded "step": gear-scan a batch of stream blocks AND
    hash a batch of chunk lanes in one compiled program.

    blocks:  uint8 [B, N]    (B % data-axis == 0, N % (32*seq-axis) == 0)
    lanes:   uint8 [L, CAP]  (L % device-count == 0, CAP % 64 == 0)
    lengths: int32 [L]
    Returns (bitmap uint32 [B, N//32], digests uint32 [L, 8]).
    """
    gear_fn = gear_bitmap_sharded(mesh, avg_bits)
    sha_fn = sha256_lanes_sharded(mesh)

    def step(blocks, lanes, lengths):
        return gear_fn(blocks), sha_fn(lanes, lengths)

    return jax.jit(step)
