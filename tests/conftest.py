"""Test config: the suite runs on the CPU with eight host devices (the
mesh the distributed tests shard over) and one persistent compile cache.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from paddle_tpu.utils.compile_cache_dir import use_compile_cache  # noqa: E402

# The suite's cost is hundreds of sub-second eager per-op compiles on
# tiny models, so every one of them is kept, whatever its compile time.
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
