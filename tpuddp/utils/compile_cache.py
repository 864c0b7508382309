"""Where compiled programs persist between processes.

Every ``__main__`` that compiles calls :func:`enable` first thing; importing
``tpuddp`` never does, so in-process tests compile uncached as before.

Where ``$JAX_COMPILATION_CACHE_DIR`` is set, the cache lives inside that
directory — it can be placed from outside. Where it is not, inside
``<checkout>/.jax_cache``, derived from this package's own location and never
from the working directory, ``tempfile``, a pid or the time: the path is part
of the cache key, so a directory that moves never hits.

Inside either, the programs go to a subdirectory named for the version of the
names device operations carry (``observability.profiling.NAMES_VERSION``).
JAX leaves metadata out of the cache key, so a directory written before the
names changed would hand back executables that still carry the old ones, and a
profile of them would read as if the step had no forward pass (measured on the
chip, PERF.md section 6, PR 24). The key is not widened instead
(``jax_compilation_cache_include_metadata_in_key``): source paths and line
numbers are metadata too, and every checkout and every edit would compile cold.
"""

from __future__ import annotations

import os

import jax

from tpuddp.observability.profiling import NAMES_VERSION

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_DEFAULT = os.path.join(_CHECKOUT, ".jax_cache")


def directory() -> str:
    """The directory the cache lives in once :func:`enable` has run."""
    return os.path.join(os.environ.get(_ENV) or _DEFAULT, NAMES_VERSION)


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_dir", directory())
    return directory()
