import os
import sys

import pytest

# The harness imports itself as ``chipbench`` and the program from src/.
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def own_compile_cache(tmp_path_factory, monkeypatch):
    """The harness's runs in these tests compile into a directory of
    their own (``JAX_COMPILATION_CACHE_DIR`` wins over the checkout's
    default), and the cache settings ``run.run`` makes are undone: the
    program's tests watch the checkout's default cache directory."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    path = str(tmp_path_factory.getbasetemp() / "jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
    jax.config.update("jax_compilation_cache_dir", path)
    compilation_cache.reset_cache()
    yield
    for n, v in before.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
