"""Every device program compiles for a v5e, checked without a chip.

The installed libtpu gives a compile-only TPU target:
``jax.experimental.topologies.get_topology_desc`` returns v5e devices
under ``JAX_PLATFORMS=cpu``, and lowering against a sharding on one of
them runs the real Mosaic / XLA:TPU compiler. So a kernel the compiler
would refuse on the chip fails here, in tier-1, before anyone spends
chip budget on it. The programs and shapes are
``benchmarks/kernel_check.py``'s table — the one the chip run compares
bit for bit — plus the four-chip step of ``parallel/``.

Two things are read from the PROCESS's backend at trace time and would
otherwise make this compile the CPU's program, not the chip's: the SHA
scan unrolls (``ops/sha256.py _unroll``) and the Pallas gate. The
fixture below makes tracing see a TPU process.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from benchmarks import kernel_check
from makisu_tpu.chunker import cdc
from makisu_tpu.ops import sha256
from makisu_tpu.parallel import (
    block_sharding,
    lane_sharding,
    lane_vec_sharding,
    make_mesh,
    snapshot_hash_step,
)


@pytest.fixture(scope="module")
def v5e():
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any refusal means "no target"
        pytest.skip(f"no compile-only v5e topology here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


@pytest.fixture(scope="module")
def traced_as_tpu():
    """Trace what a TPU process would trace. A traced program is cached
    by function and shapes, not by backend, so the caches are dropped
    on the way in (a CPU test may have traced these shapes with the
    CPU's unrolls) and on the way out (a later CPU test must not be
    handed the TPU's 12-fold unrolled scan to compile)."""
    jax.clear_caches()
    # Executables compiled for a device that is not there cannot be
    # loaded back, so they stay out of the persistent cache (which reads
    # its switch once: hence the resets).
    from jax.experimental.compilation_cache import compilation_cache
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as monkeypatch:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("MAKISU_TPU_PALLAS", raising=False)
        assert sha256._unrolls() == (3, 4)
        yield
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()
    jax.clear_caches()


_PROGRAMS = kernel_check.programs()


@pytest.mark.parametrize("program", _PROGRAMS, ids=lambda p: p.name)
def test_program_compiles_for_v5e(v5e, traced_as_tpu, program):
    sharding = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in program.shapes]
    compiled = program.fn.lower(*args, **program.static).compile()
    assert compiled is not None
    # What the benchmark reads in a device trace: every operation's
    # name path carries its step's scope, and each production kernel's
    # custom call keeps the name the roofline readers key on.
    text = compiled.as_text()
    scope = "gear_scan" if program.name.startswith("gear") else "chunk_sha"
    paths = re.findall(r'op_name="(jit\([^"]*)"', text)
    assert paths and all(f"/{scope}" in p for p in paths)
    if "pallas" in program.name:
        kernel = ("gear_bitmap_flat" if scope == "gear_scan"
                  else "sha256_lanes_pallas")
        assert re.search(rf"%{kernel}\.\d+ = [^\n]*custom-call\(", text)


def test_table_covers_the_production_shapes():
    """The table is the production shapes, not a copy of them."""
    names = {p.name for p in _PROGRAMS}
    assert {"gear_xla", "gear_pallas_start0", "gear_pallas_start128"} \
        <= names
    for cap, lanes in cdc._BUCKETS:
        assert {f"sha_xla_{lanes}x{cap}", f"sha_pallas_{lanes}x{cap}"} \
            <= names
    by_name = {p.name: p for p in _PROGRAMS}
    assert by_name["gear_pallas_start128"].shapes[0][0] \
        == (128 + cdc.BLOCK,)


def test_four_chip_step_compiles_for_v5e(v5e, traced_as_tpu):
    """parallel.snapshot_hash_step on a (data 2, seq 2) mesh at the
    production block size: the seq-axis halo is one collective-permute
    over ICI."""
    mesh = make_mesh(v5e)
    assert dict(mesh.shape) == {"data": 2, "seq": 2}
    step = snapshot_hash_step(mesh)

    def spec(shape, dtype, sharding: NamedSharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    compiled = step.lower(
        spec((2, cdc.BLOCK), jnp.uint8, block_sharding(mesh)),
        spec((8, 2048), jnp.uint8, lane_sharding(mesh)),
        spec((8,), np.int32, lane_vec_sharding(mesh))).compile()
    assert "collective-permute" in compiled.as_text()
