"""Where compiled programs are kept between processes.

A first compile of a step program takes seconds on a TPU and the dense
engine compiles one program per padded collision-round shape, so every
entry point keeps JAX's persistent compilation cache on.  The directory
is part of the cache key: it is either the one the environment names
(``JAX_COMPILATION_CACHE_DIR``) or one fixed path beside the package —
never a temporary name.  The names of the device scopes are part of the
key too (``_SETTINGS``).
"""

from __future__ import annotations

import os
import sys

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_SETTINGS = {
    # store every program, however small or quick to compile
    "jax_persistent_cache_min_compile_time_secs": 0.0,
    "jax_persistent_cache_min_entry_size_bytes": -1,
    # JAX leaves an operation's metadata out of the key, so two programs
    # that differ only in their ``jax.named_scope`` share one entry and
    # the second runs with the first one's names.  Device time is read
    # by those names (``observability/trace.py`` ``DEVICE_SCOPES``): a
    # step fetched from before a scope existed reports nothing under it
    "jax_compilation_cache_include_metadata_in_key": True,
}


def configure_compile_cache() -> None:
    """Keep the compile cache where ``JAX_COMPILATION_CACHE_DIR`` says
    and name no other directory; without it, use ``DEFAULT_CACHE_DIR``.

    Takes effect for programs compiled after the call, so entry points
    call it before their first JAX call.  Host-only apps never import
    JAX: when it is not imported yet the settings go into the
    environment, which JAX reads at import.
    """
    settings = dict(_SETTINGS)
    if not os.environ.get(CACHE_DIR_ENV):
        settings["jax_compilation_cache_dir"] = DEFAULT_CACHE_DIR
    jax = sys.modules.get("jax")
    for name, value in settings.items():
        if jax is None:
            os.environ[name.upper()] = str(value)
        else:
            jax.config.update(name, value)
