"""Where compiled programs persist between processes.

Every ``__main__`` that compiles calls :func:`enable` first thing; importing
``tpuddp`` never does, so in-process tests compile uncached as before.

Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that directory
and nothing is set here — the cache can be placed from outside. Where it is
not, the cache goes to ``<checkout>/.jax_cache``, derived from this package's
own location and never from the working directory, ``tempfile``, a pid or the
time: the path is part of the cache key, so a directory that moves never hits.
"""

from __future__ import annotations

import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_DEFAULT = os.path.join(_CHECKOUT, ".jax_cache")


def directory() -> str:
    """The directory the cache lives in once :func:`enable` has run."""
    return os.environ.get(_ENV) or _DEFAULT


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    return directory()
