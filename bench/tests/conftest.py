import os

# The benchmark's tests run on the CPU backend; the chip is reached only
# through bench/run.py on a machine that holds one.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes",
                  "jax_compilation_cache_max_size")


@pytest.fixture(autouse=True)
def _restore_compilation_cache():
    """A run turns on the persistent compilation cache for its process;
    put the process's settings back so that later tests in the same
    worker compile as they would alone."""
    saved = {k: getattr(jax.config, k) for k in _CACHE_OPTIONS}
    yield
    from jax.experimental.compilation_cache import compilation_cache
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
