"""spark_rapids_tpu: a TPU-native Spark-SQL columnar accelerator framework.

Re-creation of the capability surface of NVIDIA's RAPIDS Accelerator for
Apache Spark (reference: andygrove/spark-rapids v0.2.0-SNAPSHOT), designed
TPU-first: columnar batches are static-shape JAX arrays in HBM, operators
compile to fused XLA executables cached per batch bucket, shuffle rides
ICI collectives under shard_map, and spill management is an explicit
host-driven tier chain (HBM -> host -> disk).

Spark parity requires 64-bit longs/doubles, so x64 is enabled at import
(the reference's cuDF kernels are 64-bit native; on TPU f64 is emulated --
performance-sensitive pipelines should prefer f32/bf16 columns).
"""
import os as _os

import jax as _jax

_jax.config.update("jax_enable_x64", True)

# Persistent XLA executable cache: the engine compiles hundreds of
# small kernels per workload query, and compiled artifacts round-trip the
# disk cache across processes, so cold starts are paid once per tree.
# One place, the same in every process: JAX_COMPILATION_CACHE_DIR where
# the environment sets it (JAX reads that itself; nothing is set here),
# else `.jax_cache` in the checkout.  The path is part of the cache key's
# world, so it never moves with HOME or the host.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))
# at the default thresholds NONE of the small kernels persist and every
# run re-pays the full compile bill -- persist everything
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
_jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

__version__ = "0.2.0"

from spark_rapids_tpu import types  # noqa: E402,F401
from spark_rapids_tpu.config import RapidsConf  # noqa: E402,F401
