"""Persistent XLA compilation cache.

First-touch compiles dominate cold starts: every (prefill, decode) shape
bucket compiles on first use, and a growing conversation crossing a bucket
pays again. JAX's persistent cache keys compiled executables by (HLO,
flags, platform) on disk, so every process after the first reuses them.
The Runtime's TPU backend, chip_smoke.py and the tools enable it
(the mock backend never compiles, so it skips the setup).

Placement: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads
it, and this module sets no directory; where it is not, the cache lives
at one fixed, git-ignored path inside the checkout — the path is part of
the cache key, so a directory that moves never hits.

Names are part of a program's identity (ISSUE 24): by default JAX keys an
executable on its module with the debug info stripped, so the
``jax.named_scope`` names and source lines that a profiler trace shows for
each operation are not in the key, and a cache warmed by a build with other
names (or none) serves executables that carry those. The scope metrics of
the benchmark read the names, so here the metadata IS in the key
(``jax_compilation_cache_include_metadata_in_key``), with the checkout's
own path taken out of the source files it names
(``jax_hlo_source_file_canonicalization_regex``), so that two checkouts of
one commit still share entries. The price: an edit that moves a traced
line misses the cache for every program traced through it — one cold
start-up after such a commit, the same as after any change to the program.
"""

from __future__ import annotations

import os
import re
from typing import Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
IN_CHECKOUT_DIR = os.path.join(REPO_ROOT, ".xla_cache")
_enabled: Optional[str] = None


def enable_compilation_cache() -> str:
    """Idempotent: turn on JAX's persistent compilation cache and return
    the directory in use — ``JAX_COMPILATION_CACHE_DIR`` where set (left
    untouched), else ``IN_CHECKOUT_DIR``."""
    global _enabled
    if _enabled is not None:
        return _enabled
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = IN_CHECKOUT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache everything that took real compile time, however small the HLO
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    # scope names and source lines are part of the key, the checkout's
    # path is not (module docstring)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if not jax.config.jax_hlo_source_file_canonicalization_regex:
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(REPO_ROOT + os.sep))
    _enabled = path
    return _enabled
