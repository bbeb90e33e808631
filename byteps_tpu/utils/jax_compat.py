"""Process-level JAX set-up shared by the entry scripts and the tests:
pinning the virtual CPU mesh (``force_cpu``) and placing the persistent
compilation cache (``setup_compile_cache``).

The package targets the one installed JAX (0.9.0: ``jax.shard_map``,
``jax.lax.axis_size``, ``jax.distributed.is_initialized`` and the
``jax_num_cpu_devices`` option all exist), so nothing here shims an
older release.
"""

from __future__ import annotations

import os
import re
from typing import Optional

# <checkout>/.jax_cache (listed in .gitignore); a fixed path because the
# cache directory is part of JAX's cache key — a directory that moves
# (tempfile, pid, time) never hits
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache; returns its directory
    (None when no cache is placed). Call before the first compile.

    - ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
      sets NO directory in code — the machine's owner placed the cache
      from outside;
    - ``JAX_PLATFORMS=cpu``: no cache. Nothing compiled for the CPU mesh
      is worth keeping, and this jaxlib's XLA:CPU loader reports every
      cached executable it reads back as a machine-feature mismatch;
    - otherwise ``<checkout>/.jax_cache``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def force_cpu(n_devices: int = 8) -> None:
    """Pin jax to an ``n_devices``-wide virtual CPU mesh. Call before the
    first device query; sets XLA_FLAGS too so a child process that has
    not imported jax yet inherits the device count. An inherited flag
    with a DIFFERENT count is rewritten, not kept — a pytest parent's
    8-device XLA_FLAGS must not override a worker child's
    force_cpu(4)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       f"--xla_force_host_platform_device_count"
                       f"={n_devices}", flags)
        os.environ["XLA_FLAGS"] = flags
    else:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
