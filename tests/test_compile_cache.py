"""Where the entry points put JAX's persistent compilation cache.

``jax.config.update`` is recorded rather than applied, so the test
process never turns the cache on.
"""

import jax
import pytest

from repro.launch import compile_cache


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"],
                         ids=["fixed_in_checkout", "from_environment"])
def test_use_compile_cache_places_the_cache(monkeypatch, env_dir):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)

    path = compile_cache.use_compile_cache()

    if env_dir is None:
        # a fixed directory at the repository root, never a temporary one
        assert path == str(compile_cache.REPO_CACHE_DIR)
        assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
        assert (compile_cache.REPO_CACHE_DIR.parent / "src" / "repro"
                ).is_dir()
        assert calls["jax_compilation_cache_dir"] == path
    else:
        # JAX reads the variable itself; no directory is set in code
        assert path == env_dir
        assert "jax_compilation_cache_dir" not in calls
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0
