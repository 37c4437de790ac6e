"""Where JAX keeps its persistent compilation cache.

Called once by each entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``) before it compiles anything; never on library import
and never in tests.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory itself and
this sets nothing.  Otherwise the cache goes to ``.jax_cache/`` at the repo
root: a fixed path, so that the next run finds what this one compiled (a
directory named from a temporary name, a pid or the time never would).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns that path."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
