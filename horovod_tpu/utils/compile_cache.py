"""Where the persistent XLA compile cache lives.

Called by entry programs (chip_smoke.py, benchmark/run.py, the jax
examples) before their first compile — never by the
library's import or `hvd.init()`: a library that relocates a user's
cache is a surprise.

The directory is part of every cache key, so it must not move between
runs: it is `JAX_COMPILATION_CACHE_DIR` when the environment sets one
(jax reads the variable itself; nothing is set in code), else one fixed
path inside the checkout. Never a temp dir, a pid or a timestamp.
"""
from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Returns the cache directory in effect. With the variable unset,
    exports the in-checkout default so launched workers share it, and
    tells an already-imported jax (which read the environment at import
    time). Does not import jax and initialises no backend."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    os.environ[ENV_VAR] = DEFAULT_DIR
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
