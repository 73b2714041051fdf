"""The persistent compilation cache lands where JAX_COMPILATION_CACHE_DIR
says, else at the fixed .jax_cache directory of the checkout."""
import os

import jax

from repro.launch import compile_cache as CC

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spy(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_var_places_the_cache(monkeypatch, tmp_path):
    calls = _spy(monkeypatch)
    monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
    assert CC.init_compile_cache() == str(tmp_path)
    assert not [c for c in calls if c[0] == "jax_compilation_cache_dir"]


def test_default_is_the_fixed_checkout_dir(monkeypatch):
    calls = _spy(monkeypatch)
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    first = CC.init_compile_cache()
    second = CC.init_compile_cache()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2
