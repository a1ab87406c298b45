"""Start-up shared by the launchers and ``chip_smoke.py``.

Two rules every entry point that runs on a chip follows:

* **Compile cache.** JAX's persistent compilation cache is on. Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
  sets another directory. Otherwise the cache is the fixed
  ``<repo>/.jax_cache``: the path is part of what a later run must find, so
  it is never built from a temporary name, a process id or the time.
* **No hidden fallback.** ``--backend pallas`` (the default) needs a TPU; a
  host without one fails unless ``--backend ref`` is asked for explicitly.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO_ROOT / ".jax_cache"
BACKENDS = ("pallas", "ref")


def use_compile_cache() -> str:
    """Give the persistent compilation cache its directory; returns it.

    Call before the first compile: JAX settles whether the cache is used
    when it first compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def check_backend(backend: str) -> None:
    """Refuse the Pallas backend on a host whose JAX platform is not a TPU."""
    platform = jax.default_backend()
    if backend == "pallas" and platform != "tpu":
        raise SystemExit(
            f"--backend pallas needs a TPU, but JAX's platform is "
            f"{platform!r}; pass --backend ref to run the reference path")
