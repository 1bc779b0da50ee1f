"""Process-level JAX set-up shared by every entry point: the ONE
persistent compile cache, and the device label printed beside every
measurement.

A chip belongs to one process, and each process compiles from nothing
unless it finds a persistent cache — so every first toucher of JAX
(filter backends at open, ``tensor_llm.start``, ``launch.py``, the bench
child, ``chip_smoke.py``) calls :func:`enable_compile_cache`, and all of
them land in the same directory.  The directory is part of the cache
key's path, so it must not move between runs: it is
``JAX_COMPILATION_CACHE_DIR`` when the environment sets one (JAX reads
that itself; nothing here overrides it), else a fixed git-ignored
directory inside the checkout.
"""

from __future__ import annotations

import os
from typing import Any, Dict

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: where compiled executables persist when the environment names no
#: directory (listed in .gitignore; empty in a fresh checkout)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

#: executables that compiled faster than this are not worth a file
MIN_CACHED_COMPILE_SECS = 0.5


def enable_compile_cache() -> str:
    """Switch on JAX's persistent compilation cache and return its
    directory: the environment's ``JAX_COMPILATION_CACHE_DIR`` when set
    (JAX reads that itself — no directory is set in code), else
    :data:`DEFAULT_COMPILE_CACHE_DIR`.  Call before the first compile;
    repeat calls are harmless (same values every time)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_CACHED_COMPILE_SECS)
    # a profiler trace names a device operation from the metadata of the
    # executable that ran (``jax.named_scope``: ``llm.engine.step``,
    # ``sflm.attn``), and JAX leaves metadata out of the cache key by
    # default: a cache filled before a scope was added or renamed would
    # go on serving executables that carry the old names
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    return path


def device_label() -> Dict[str, Any]:
    """``platform`` / ``device_kind`` / ``device_count`` as JAX reports
    them — stamped on every bench row and the chip-smoke summary, so a
    number can never be read apart from the device it came from."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}
