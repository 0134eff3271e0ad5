"""Persistent XLA compilation cache: one policy, placed from outside.

Large jitted programs (the BERT-base train step, every serving prefill
bucket) pay tens of seconds of trace+lower+compile on first call.  JAX
ships a persistent on-disk compilation cache that skips that cost across
process restarts; this module is the single place the repo turns it on, so
the trainer, ``InferenceEngine``, ``MultiLayerNetwork`` and
``chip_smoke.py`` all share one policy:

- ``JAX_COMPILATION_CACHE_DIR`` is set: JAX itself reads it, the cache
  lives there, and this module sets no directory at all.
- It is not set: the cache lives at the fixed ``<checkout>/.cache/xla``
  (gitignored), derived from this package's own location.  The path is
  part of the cache key, so it never depends on a pid, a clock or a
  temporary name.
- JAX's own switch (``jax_enable_compilation_cache`` /
  ``JAX_ENABLE_COMPILATION_CACHE=false``) turns the whole thing off; the
  test suite uses it so that tests never write into the checkout's cache.

Either way the thresholds are lowered so small programs are cached too, and
the cache key includes the programs' metadata (source locations and
``jax.named_scope`` paths).  JAX leaves it out by default, so that an edit
which only moves lines still hits; but an executable read back under such a
key carries the metadata of whichever program was compiled first, and a
profiler trace then shows stale names, or none, for the sublayers
(DESIGN.md §9; measured in PERF.md §6, PR 25: the parent commit's run read
this commit's executable, scopes and all).  Names in a trace are what the
benchmark's per-sublayer metrics are read from, so they have to be this
program's own: an edit that moves lines costs one compile.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = str(Path(__file__).resolve().parents[2] / ".cache" / "xla")

_lock = threading.Lock()
_configured = False


def setup_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache under the module's
    policy.  Returns the cache directory, or ``None`` when JAX's own
    switch has the cache disabled.  Idempotent and process-global: safe to
    call from every trainer/engine/network constructor."""
    global _configured
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    with _lock:
        if not _configured:
            if not os.environ.get(ENV_DIR):
                from jax.experimental.compilation_cache import (
                    compilation_cache as jax_cc)

                jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
                # a compile that ran under another directory latched it
                jax_cc.reset_cache()
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
            jax.config.update(
                "jax_compilation_cache_include_metadata_in_key", True)
            _configured = True
        return jax.config.jax_compilation_cache_dir


def _reset_for_tests() -> None:
    """Forget that this process was configured (jax config is untouched)."""
    global _configured
    with _lock:
        _configured = False
