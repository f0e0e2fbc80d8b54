"""JAX's persistent compilation cache, in one place.

``JAX_COMPILATION_CACHE_DIR``, where it is set, names the directory and
JAX reads it itself. Otherwise the cache lives at a fixed
``<checkout>/.jax_cache``. The variable is exported either way, so child
processes (procmode ranks, the CPU-mesh sweep) share the cache. A copy of
the tree at another path was seen to compile the flagship step cold from
a cache that held it (chip run, PR 21); why is not known.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable() -> str:
    """Turn the cache on for this process and its children; returns the
    directory."""
    import jax

    # JAX's own variable, not an MCA knob: it must reach jax and children
    path = os.environ.setdefault(  # mpilint: disable=raw-environ
        "JAX_COMPILATION_CACHE_DIR", os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
