"""core.compile_cache: the persistent XLA cache is placed from outside.

``JAX_COMPILATION_CACHE_DIR`` set -> the code sets no directory (JAX reads
the variable itself); unset -> ``<repo>/.jax_cache``. Thresholds are set
either way, and the two entry points turn the cache on by themselves.
"""
import os

import jax
import pytest

from paddle_tpu.core import compile_cache

_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_state(monkeypatch):
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    monkeypatch.setattr(compile_cache, "_done", False)
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_env_places_the_cache_and_code_sets_no_directory(monkeypatch,
                                                         tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", "left-to-jax")
    assert compile_cache.ensure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == "left-to-jax"
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 2
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_without_env_the_cache_is_repo_jax_cache(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_REPO, ".jax_cache")
    assert compile_cache.ensure() == want
    assert jax.config.jax_compilation_cache_dir == want
    jax.config.update("jax_compilation_cache_dir", "not-again")
    assert compile_cache.ensure() == want        # idempotent: one placement
    assert jax.config.jax_compilation_cache_dir == "not-again"


def test_entry_points_turn_the_cache_on(monkeypatch):
    from paddle_tpu.distributed.plan import Plan
    from paddle_tpu.models import llama
    from paddle_tpu import serving

    cfg = llama.preset("llama-debug")
    Plan().train_step(cfg, jax.devices()[:1], verify=False)
    assert compile_cache._done
    monkeypatch.setattr(compile_cache, "_done", False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    serving.LLMEngine(cfg, params, max_running=2)._step_fn(1)
    assert compile_cache._done
