"""What the attached device decides: the Pallas execution mode and where
JAX keeps its persistent compilation cache.

* :func:`resolve_interpret` — every kernel entry point takes
  ``interpret=None`` and resolves it here: Mosaic-compiled on a TPU,
  the Pallas interpreter everywhere else.  Passing a bool overrides.
* :func:`enable_compile_cache` — the launchers and ``chip_smoke.py`` call it
  before their first compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
  the cache (JAX reads it itself; no other directory is set in code);
  otherwise the cache lives at :data:`DEFAULT_COMPILE_CACHE`, a fixed path
  inside the checkout, so every run from the same checkout finds it.
"""
from __future__ import annotations

import os
from typing import Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: repository root when running from a source checkout (``src/repro/...``)
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_COMPILE_CACHE = os.path.join(_ROOT, ".jax_compile_cache")


def on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas interpret mode: ``None`` means interpret unless on a TPU."""
    return not on_tpu() if interpret is None else bool(interpret)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = DEFAULT_COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
