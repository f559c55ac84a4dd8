"""TPU compute kernels for the layer-commit hot path.

The reference's hot loop (lib/builder/step/common.go:35-67) streams layer-tar
bytes through two sequential SHA-256 digesters on CPU. Here the equivalent
work is re-designed data-parallel for the TPU VPU:

- ``sha256``: SHA-256 over many independent lanes (chunks) at once. Each
  uint32 op in the compression function is an elementwise op over a lane
  vector, so 1024+ messages hash in lock-step on the 8x128 VPU.
- ``gear``: Gear rolling-hash content-defined chunking. The sequential
  recurrence ``h_i = (h_{i-1} << 1) + G[b_i] (mod 2^32)`` is exactly a
  32-byte windowed correlation (terms older than 32 bytes shift out mod
  2^32), computed in 5 log-doubling steps — fully parallel over positions.
"""

import os as _os

import jax as _jax

# THE compile-cache site (this package is imported before any jit
# traces). A directory placed from outside wins and the program sets no
# other; otherwise the cache lives at a fixed path in the checkout —
# never a temporary name, a pid or a time: a directory that moves never
# hits. Without a cache every `makisu-tpu build` / `worker` process
# compiles the gear program and both SHA bucket programs cold.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__)))), ".jax_cache"))
# Cache every program: the Pallas kernels compile in about a second,
# right at JAX's default 1s threshold, so which of them a process
# recompiles would otherwise depend on the day.
if not _os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

from makisu_tpu.ops import gear, sha256  # noqa: E402,F401
