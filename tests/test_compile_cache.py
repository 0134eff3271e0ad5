"""Persistent-compilation-cache policy: placed from outside through
``JAX_COMPILATION_CACHE_DIR``, else at one fixed in-checkout path; the
suite itself is opted out through JAX's own switch (conftest.py)."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from deeplearning4j_tpu.parallel import compile_cache as cc

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_on(monkeypatch):
    """Re-enable the cache the suite turns off, record every
    ``jax.config.update`` the module makes, and put jax's config and the
    module's process-global state back afterwards."""
    saved = {n: getattr(jax.config, n) for n in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_compilation_cache_include_metadata_in_key")}
    monkeypatch.delenv(cc.ENV_DIR, raising=False)
    cc._reset_for_tests()
    real_update = jax.config.update
    real_update("jax_enable_compilation_cache", True)
    updates = []

    def spy(name, value):
        updates.append(name)
        real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    yield updates
    for n, v in saved.items():
        real_update(n, v)
    cc._reset_for_tests()


def test_env_set_means_code_sets_no_directory(tmp_path, monkeypatch,
                                              cache_on):
    d = str(tmp_path / "from-env")
    monkeypatch.setenv(cc.ENV_DIR, d)
    # jax reads the variable itself at import; stand in for that here
    jax.config.update("jax_compilation_cache_dir", d)
    cache_on.clear()
    assert cc.setup_compile_cache() == d
    assert "jax_compilation_cache_dir" not in cache_on
    # ... but the thresholds were lowered so small programs get in
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_env_unset_means_fixed_in_checkout_path(cache_on):
    assert cc.DEFAULT_DIR == str(REPO / ".cache" / "xla")
    assert cc.setup_compile_cache() == cc.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_DIR
    assert jax.config.jax_enable_compilation_cache is True


def test_cache_key_includes_the_metadata(cache_on):
    """An executable read back from the cache must carry THIS program's
    ``jax.named_scope`` paths, or a profiler trace shows another's names."""
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    cc.setup_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_idempotent(cache_on):
    assert cc.setup_compile_cache() == cc.DEFAULT_DIR
    cache_on.clear()
    # later callers (trainer/engine/multilayer constructors) get the same
    # directory back without touching jax config again
    assert cc.setup_compile_cache() == cc.DEFAULT_DIR
    assert cache_on == []


def test_path_is_the_same_in_another_process(tmp_path):
    """The path is part of the cache key: it must not move with the cwd,
    the pid or the time a process starts."""
    env = {k: v for k, v in os.environ.items() if k != cc.ENV_DIR}
    env["PYTHONPATH"] = str(REPO)
    proc = subprocess.run(
        [sys.executable, "-c",
         "from deeplearning4j_tpu.parallel import compile_cache as cc; "
         "print(cc.DEFAULT_DIR)"],
        capture_output=True, text=True, timeout=120, env=env, cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-500:]
    assert proc.stdout.split()[-1] == cc.DEFAULT_DIR


def test_suite_opt_out():
    """conftest.py switches the cache off with JAX's own flag, in-process
    and for every subprocess: setup is then a no-op and nothing the suite
    compiles can land in the checkout's ``.cache/xla``."""
    assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"
    assert jax.config.jax_enable_compilation_cache is False
    before = jax.config.jax_compilation_cache_dir
    assert cc.setup_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
