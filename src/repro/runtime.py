"""Process-level JAX setup shared by the entry points.

Two concerns that every program touching the accelerator has, kept out of
import time so importing ``repro`` never changes JAX state:

- `enable_compilation_cache` — JAX's persistent compilation cache. If
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
  configured here; otherwise the cache lives at a fixed path inside the
  checkout (``<repo>/.jax_cache``, git-ignored). A cache is found again
  only at the same path, so the path never depends on a temp name, pid or
  time.
- `require_chip_free` — an accelerator belongs to one process at a time.
  A parent that has initialised a non-CPU backend holds the chip, and a
  child process that needs it then fails or hangs; code that is about to
  start JAX children calls this first and gets a clear error instead.
"""

from __future__ import annotations

import os
from typing import Optional

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Call once near the top of a program's ``main`` (never on import).
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def held_accelerator() -> Optional[str]:
    """Platform of the non-CPU backend this process holds, else None.

    Never initialises a backend itself: a process that has not touched
    JAX's devices yet holds nothing.
    """
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    import jax

    platform = jax.default_backend()
    return None if platform == "cpu" else platform


def require_chip_free(what: str) -> None:
    """Raise if this process holds an accelerator that ``what`` (a JAX
    child process or pool) would need."""
    platform = held_accelerator()
    if platform is not None:
        raise RuntimeError(
            f"{what} starts JAX child processes, but this process already "
            f"holds the {platform} backend: a chip belongs to one process "
            f"at a time, so the child would fail or hang. Run it from a "
            f"process that has not touched JAX's devices, or in-process."
        )
