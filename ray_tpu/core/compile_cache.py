"""Where compiled XLA programs persist between processes.

Every process that compiles for the chip (TPU workers, the serving
engines, the train backend) calls ``ensure_compile_cache`` first, so a
replica that replaces another — or the next run on the same machine —
loads its programs instead of compiling them again.
"""

from __future__ import annotations

import os
import sys
from typing import MutableMapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"  # jax's own; read at ``import jax``
_METADATA_IN_KEY = "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"  # too

# One fixed directory inside the checkout (git-ignored). Fixed because the
# path takes part in the cache key: a per-session or per-pid directory
# would never hit.
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def ensure_compile_cache(env: MutableMapping[str, str] = os.environ) -> str:
    """Return the persistent compile-cache directory, arranging for jax to
    use it.

    If ``JAX_COMPILATION_CACHE_DIR`` is set it wins and no other
    directory is set — jax reads it. Otherwise the fixed in-checkout directory is
    written into ``env`` (so children inherit it and a later ``import
    jax`` reads it) and, when ``env`` is this process's environment and
    jax is already imported, into jax's config.
    """
    live = env is os.environ and "jax" in sys.modules
    # The cache key leaves a program's metadata out unless told otherwise,
    # and ``jax.named_scope`` is metadata: a program cached before a scope
    # was added would be handed back without it, and a profiler trace of
    # it would name nothing. With metadata in the key such a program is
    # compiled once more.
    env[_METADATA_IN_KEY] = "1"
    if live:
        sys.modules["jax"].config.update(_METADATA_IN_KEY.lower(), True)
    path = env.get(ENV_VAR)
    if path:
        return path
    env[ENV_VAR] = _DEFAULT_DIR
    if live:
        sys.modules["jax"].config.update("jax_compilation_cache_dir",
                                         _DEFAULT_DIR)
    return _DEFAULT_DIR
