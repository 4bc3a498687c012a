import jax
import pytest


@pytest.fixture(autouse=True, scope="session")
def no_persistent_cache():
    # the tests compile tiny CPU programs: keep them out of the
    # checkout's compile cache, which the chip runs use
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
