"""Launch layer: production meshes, dry-run driver, train/serve CLIs."""
import os
from pathlib import Path

#: JAX's persistent compilation cache for the entry points when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: one fixed directory of the
#: checkout, since the path is part of what the cache is keyed on.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.

    Entry points call this once before compiling anything; tests never do.
    A set ``JAX_COMPILATION_CACHE_DIR`` is left to JAX untouched.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
