"""``repro.compile_cache.place()`` puts JAX's persistent cache at its one
home: ``$JAX_COMPILATION_CACHE_DIR`` with JAX's own settings when that is
set, else ``<checkout>/.jax_cache`` with every program admitted."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_off():
    yield
    compile_cache.use(None)


def _config():
    return (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_entry_size_bytes,
            jax.config.jax_persistent_cache_min_compile_time_secs)


@pytest.mark.parametrize("env", [False, True], ids=["checkout", "env"])
def test_place_puts_the_cache_at_its_home(env, tmp_path, monkeypatch,
                                          cache_off):
    if env:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        want = (str(tmp_path), 0, 1.0)          # JAX's default floors
    else:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        want = (str(compile_cache.CHECKOUT_CACHE), -1, 0.0)
    # a use() elsewhere (benchmarks/portfolio.py's cold/warm section) is undone
    compile_cache.use(str(tmp_path / "elsewhere"))
    assert compile_cache.place() == want[0]
    assert _config() == want
