"""Process-level jax set-up shared by the entry points.

One helper: :func:`setup_compile_cache`. Entry points that compile real
programs (``chip_smoke.py``) call it before their first
compilation so every process of one checkout shares one persistent compilation cache. The directory is part of the cache key, so it
is a FIXED path — never a temp name, pid or timestamp, which would never hit.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def setup_compile_cache() -> str:
    """Return the persistent compilation cache directory, configuring it when
    the environment did not.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set: jax reads it at import and
    nothing else is set here. Otherwise the cache lives in
    ``<checkout>/.jax_cache`` (git-ignored), set through ``jax.config``
    (importing this package has already imported jax, so the env var would
    be too late; the config takes effect until the first compilation).
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        import jax

        cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
