"""Process-level JAX backend setup for the entry points that own the
device (`serened`, bench children, `__graft_entry__`).

Nothing here runs at import: an accelerator belongs to one process at a
time, so only an entry point that means to dispatch calls
`init_backend()` — once, before its first compile.
"""

from __future__ import annotations

import os
import re

#: <checkout>/.jax_cache — a FIXED path, because the cache directory is
#: part of XLA's cache key: a directory that moves never hits
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere and return
    the directory. Where `JAX_COMPILATION_CACHE_DIR` is set the
    environment owns the placement (jax reads the variable itself) and
    no directory is set in code; otherwise the cache lives at
    `<checkout>/.jax_cache`. Every compile is cached, however short —
    a server restart repeats hundreds of small programs."""
    import jax
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        cache_dir = env_dir
    else:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # a Pallas kernel travels inside its program as serialized MLIR that
    # names the source files of its call stack, and that text is part of
    # the cache key: name them from the checkout's root, or a checkout
    # that moved would compile every histogram program again
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(CHECKOUT_ROOT + os.sep))
    return cache_dir


def init_backend() -> dict:
    """Configure the compile cache, initialize the JAX backend, and say
    what it is: {"platform", "device_kind", "count", "cache_dir"} as
    jax reports them. Raises whatever jax raises when the configured
    platform cannot start — an entry point must not run somewhere else
    without saying so."""
    import jax

    from ..parallel import mesh
    cache_dir = configure_compile_cache()
    devs = jax.devices()
    mesh.note_backend_initialized()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "count": len(devs),
            "cache_dir": cache_dir}
