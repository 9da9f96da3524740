"""Persistent XLA compilation cache for the entry points.

Called from ``main()`` of a CLI (``repro.launch.insitu``,
``chip_smoke.py``), never at import: a library import must not change
process-wide JAX configuration.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["configure_compile_cache"]


def configure_compile_cache(checkout: Path) -> str:
    """Use ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself and nothing is overridden); otherwise cache compiled programs
    in ``<checkout>/.jax_cache``.  The path is fixed so that a later run
    in the same checkout finds what an earlier one compiled.  Returns the
    directory in use.

    The cache is keyed on the programs' metadata too: by default JAX
    strips it from the key, so a program that differs from a cached one
    only in its named scopes loads the cached executable and profiles
    under the old scopes.  Source paths in the metadata are cut to file
    names, so that a checkout elsewhere still finds its entries."""
    jax.config.update("jax_hlo_source_file_canonicalization_regex", r".*/")
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(checkout) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
