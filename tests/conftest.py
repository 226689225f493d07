import jax
import jax.numpy as jnp
import pytest

from repro.core.compile_cache import enable_compile_cache

# NOTE: no XLA_FLAGS here — smoke tests and benches see 1 device; only
# launch/dryrun.py (run as its own process) forces 512 host devices.

# Persistent XLA compile cache (keyed by HLO): identical programs built by
# different jit instances — e.g. the eval fn across every run_federated call,
# or a step fn shared by two tests — compile once per machine instead of once
# per LocalTrainer.  This is what keeps the tier-1 lane fast.
enable_compile_cache()


def pytest_collection_modifyitems(config, items):
    """Everything not marked ``slow`` is tier-1 (the default `pytest -q` run,
    see pytest.ini); tag it so `-m tier1` selects the same subset."""
    for item in items:
        if "slow" not in item.keywords:
            item.add_marker(pytest.mark.tier1)


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.key(0)


def small_params(key=None):
    """A small transformer-shaped pytree used across partition tests."""
    key = key if key is not None else jax.random.key(0)
    ks = jax.random.split(key, 8)
    return {
        "embed": {"table": jax.random.normal(ks[0], (32, 16))},
        "blocks": {
            "0": {"attn": {"wq": {"w": jax.random.normal(ks[1], (16, 16))},
                           "wo": {"w": jax.random.normal(ks[2], (16, 16))}},
                  "norm": {"scale": jnp.ones(16)}},
            "1": {"attn": {"wq": {"w": jax.random.normal(ks[3], (16, 16))},
                           "wo": {"w": jax.random.normal(ks[4], (16, 16))}},
                  "norm": {"scale": jnp.ones(16)}},
            "2": {"attn": {"wq": {"w": jax.random.normal(ks[5], (16, 16))},
                           "wo": {"w": jax.random.normal(ks[6], (16, 16))}},
                  "norm": {"scale": jnp.ones(16)}},
        },
        "head": {"w": jax.random.normal(ks[7], (16, 8)), "b": jnp.zeros(8)},
    }


@pytest.fixture
def params():
    return small_params()
