"""flatten/unflatten round-trip tests (apex_C equivalent)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import flatten, flatten_like, unflatten
from apex_tpu.ops.flatten import flatten_grouped


def test_roundtrip():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.float32),
                  "d": jnp.asarray(5.0)}}
    flat, spec = flatten(tree)
    assert flat.shape == (6 + 4 + 1,)
    back = unflatten(flat, spec)
    for l1, l2 in zip(jax.tree_util.tree_leaves(tree),
                      jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_dtype_promotion_and_cast_back():
    tree = {"h": jnp.ones((3,), jnp.bfloat16), "f": jnp.ones((3,), jnp.float32)}
    flat, spec = flatten(tree)
    assert flat.dtype == jnp.float32
    back = unflatten(flat, spec)
    assert back["h"].dtype == jnp.bfloat16
    back32 = unflatten(flat, spec, cast_back=False)
    assert back32["h"].dtype == jnp.float32


def test_flatten_like_reuses_spec():
    tree = {"a": jnp.ones((2, 2)), "b": jnp.zeros((3,))}
    flat, spec = flatten(tree)
    tree2 = jax.tree_util.tree_map(lambda x: x * 2, tree)
    flat2 = flatten_like(tree2, spec)
    np.testing.assert_array_equal(np.asarray(flat2), np.asarray(flat) * 2)


def test_empty_tree():
    flat, spec = flatten({})
    assert flat.shape == (0,)
    assert unflatten(flat, spec) == {}


def test_jit_roundtrip():
    tree = {"a": jnp.ones((7,)), "b": jnp.full((5,), 2.0)}
    _, spec = flatten(tree)

    @jax.jit
    def f(t):
        fl = flatten_like(t, spec)
        return unflatten(fl * 2, spec)

    out = f(tree)
    np.testing.assert_array_equal(np.asarray(out["b"]), 4.0)


def _mixed_tree():
    """The leaf shapes of a GPT block beside a scalar and a half leaf:
    minor dimensions 64, 4,096 and none."""
    rng = np.random.RandomState(0)

    def leaf(*shape, dtype=jnp.float32):
        return jnp.asarray(rng.randn(*shape), dtype)

    return {"bias": leaf(16, 64), "kernel": leaf(1024, 16, 64),
            "mlp_bias": leaf(4096), "scale": leaf(),
            "half": leaf(24, 8, dtype=jnp.bfloat16)}


def _in_jit(f, tree):
    return jax.jit(f)(tree)


def _in_shard_map(f, tree):
    """Every device holds the whole tree and cuts it: the manual region
    ``zero2_update`` cuts its leaves in."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(),
                                 out_specs=P()))(tree)


@pytest.mark.parametrize("cast_back", [True, False])
@pytest.mark.parametrize("under", [_in_jit, _in_shard_map])
def test_unflatten_inverts_flatten_over_mixed_shapes(under, cast_back):
    tree = _mixed_tree()
    _, spec = flatten(tree)

    def roundtrip(t):
        return unflatten(flatten_like(t, spec), spec, cast_back=cast_back)

    back = under(roundtrip, tree)
    assert jax.tree.structure(back) == jax.tree.structure(tree)
    for want, got in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert got.shape == want.shape
        assert got.dtype == (want.dtype if cast_back else jnp.float32)
        # bfloat16 -> float32 -> bfloat16 is exact
        np.testing.assert_array_equal(np.asarray(want, np.float32),
                                      np.asarray(got, np.float32))


@pytest.mark.parametrize("under_mesh", [False, True],
                         ids=["update_slices", "concatenate_under_mesh"])
def test_flatten_like_fills_the_buffer_as_flatten_laid_it_out(under_mesh):
    """Both forms of the gather (one buffer filled by
    ``dynamic_update_slice``; a ``concatenate`` where the partitioner
    owns mesh axes), on a grouped layout with a padded tail."""
    import contextlib
    from jax.sharding import Mesh
    tree = _mixed_tree()
    flat, spec = flatten_grouped(tree, [1, 0, 1, 0, 1], dtype=jnp.float32,
                                 pad_to=128)
    assert spec.perm and flat.shape[0] > spec.total
    scope = (Mesh(np.asarray(jax.devices()[:2]), ("data",)) if under_mesh
             else contextlib.nullcontext())
    with scope:
        again = jax.jit(lambda t: flatten_like(
            t, spec, dtype=jnp.float32, pad_to=128))(tree)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(again))
