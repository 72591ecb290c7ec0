"""Where JAX's persistent compilation cache lives.

A cold process recompiles every program it runs (the gpt2-small train step
and the paged engine's tick programs take tens of seconds each on a chip),
and the cache's directory is part of its key, so a directory that moves
never hits. The place is therefore decided outside the program when
`JAX_COMPILATION_CACHE_DIR` is set, and is one fixed path inside the
checkout otherwise — never a temp name, a pid or a timestamp.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def ensure_compile_cache() -> str:
    """Make sure a persistent compilation cache is configured before the
    first compile and return its directory. Called from `ray_tpu.init()`
    and from the bench/smoke entry points.

    With `JAX_COMPILATION_CACHE_DIR` set this does nothing: JAX reads the
    variable itself and no other directory is set in code. Unset, the
    cache goes to `<checkout>/.jax_cache`: exported through the same
    variable, so a later `import jax` and every child process agree on it
    without this function importing JAX, and applied to an already
    imported JAX directly."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    os.environ[ENV_VAR] = REPO_CACHE_DIR
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
