"""Launchers: production mesh, multi-pod dry-run, train/serve drivers."""
from __future__ import annotations

import os

# the checkout root: src/repro/launch/__init__.py -> three levels up
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the place (jax reads it
    itself, so nothing else is set). Otherwise the cache lives at the
    fixed ``.jax_cache/`` of the checkout — never a temp, pid or time
    name — so a later run of the same checkout finds what an earlier one
    wrote. Called from the entry points' ``main()``, never at import.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
