"""Activation-sharding context tests."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_host_mesh
from repro.models.partitioning import activation_sharding, default_rules, shard_act


def test_identity_without_context(rng):
    x = jax.random.normal(rng, (4, 8))
    y = shard_act(x, ("batch", "embed"))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_with_single_device_mesh(rng):
    mesh = make_host_mesh(1, 1)
    x = jax.random.normal(rng, (4, 6, 8))

    @jax.jit
    def f(x):
        with activation_sharding(mesh):
            return shard_act(x, ("batch", "seq", "ff")) * 2

    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x) * 2)


def test_divisibility_guard():
    mesh = make_host_mesh(1, 1)
    rules = default_rules(mesh)
    assert rules["heads"] == "model"
    # dims not divisible by the axis are left unsharded -> no error
    x = jnp.zeros((3, 5, 7))
    with activation_sharding(mesh):
        y = shard_act(x, ("batch", "seq", "heads"))
    assert y.shape == x.shape


def test_rank_mismatch_is_noop(rng):
    mesh = make_host_mesh(1, 1)
    x = jax.random.normal(rng, (4, 8))
    with activation_sharding(mesh):
        y = shard_act(x, ("batch", "seq", "heads"))  # wrong rank
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
