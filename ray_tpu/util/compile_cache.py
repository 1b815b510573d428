"""Where XLA's compiled programs are kept between processes and runs.

JAX's persistent compilation cache is keyed by its directory among other
things, so the directory must not move: every worker of a run, and the next
run, only find what was compiled before at the same path.

**Metadata is part of the key.** By default this jax leaves an instruction's
metadata (its ``op_name``, the file and line) out of the key, so a program
that differs from a cached one by its scope names alone (the stages of
``models/common.py:stage``) would be handed the old executable, with the old
names, and a device trace read by stage would read another commit's stages or
none. With the metadata in the key that cannot happen; the price is that an
edit which moves a traced line compiles its programs again.
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# The environment form of jax_compilation_cache_include_metadata_in_key.
METADATA_IN_KEY_ENV = "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"
# <checkout>/.jax_cache (listed in .gitignore), from this file's location.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def ensure_compile_cache() -> str:
    """Make sure JAX_COMPILATION_CACHE_DIR is set and return it.

    A directory placed from outside stands; otherwise the fixed one inside
    the checkout is used. Wherever the cache is, an entry is found only by a
    program of the same metadata (module docstring). Only environment
    variables are set (jax reads them when it is imported, so call this
    first), and worker processes inherit them from whoever spawned them."""
    os.environ[METADATA_IN_KEY_ENV] = "true"
    return os.environ.setdefault(CACHE_DIR_ENV, DEFAULT_CACHE_DIR)


class CacheCounter:
    """This process's persistent-cache hits and misses from the moment the
    counter is made, so make it before the programs of interest compile.
    A compilation too quick to be worth caching counts as neither."""

    def __init__(self):
        import jax.monitoring

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {
            "dir": os.environ.get(CACHE_DIR_ENV),
            "hits": self.hits,
            "misses": self.misses,
        }
