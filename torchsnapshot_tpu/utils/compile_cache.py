"""Where the repo's entry points keep JAX's persistent compilation cache.

The cache key includes the cache path, so a directory that moves never
hits: the path is either the one the caller's environment names or one fixed
directory inside the checkout.  Nothing here runs at import; ``chip_smoke.py``
and ``__graft_entry__.py`` call :func:`place_compile_cache` before their
first compile, and the library itself sets no cache.
"""

from __future__ import annotations

import os

# <checkout>/.jax_compile_cache, from this file's own location
# (<checkout>/torchsnapshot_tpu/utils/compile_cache.py); listed in .gitignore.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_compile_cache",
)


def place_compile_cache() -> str:
    """Returns the cache directory in effect.  With
    ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it and this sets nothing.
    Otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`, and the minimum
    compile time for an entry to be kept drops from JAX's 1 s to 0: the
    library's jitted helpers (u8 repack and unpack, device copy) each compile
    in well under a second, so at the default none of them would be cached."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return CHECKOUT_CACHE_DIR
