"""``ops.short_conv``: the Pallas kernel, in interpret mode, against its
plain ``jnp`` form, and that form against the short convolution over
whole rows (``models.lfm2_moe.causal_short_conv``) when a sequence is fed
a launch at a time."""

import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.lfm2_moe import causal_short_conv
from apex_tpu.ops.short_conv import short_conv, short_conv_reference

W, RING, SLOTS = 256, 16, 8


@pytest.mark.parametrize("b,s,starts,lengths,rings,consecutive", [
    # a chunk: one row at its slot's ring, shorter and longer than it
    (1, 16, [5], [7], [2], False),
    (1, 16, [0], [16], [1], False),
    (1, 40, [13], [37], [3], False),
    (1, 40, [1000], [40], [7], False),
    # decode and verify launches of the whole batch, idle rows among them
    (8, 1, [0, 10, 20, 30, 40, 50, 60, 70], [1, 1, 0, 1, 1, 1, 0, 1],
     list(range(8)), True),
    (8, 5, [3, 0, 17, 40, 2, 9, 1, 33], [5, 3, 0, 1, 5, 2, 4, 5],
     list(range(8)), True),
    (3, 5, [3, 0, 17], [5, 3, 0], [0, 1, 2], True),
])
def test_the_kernel_is_its_plain_form(b, s, starts, lengths, rings,
                                      consecutive):
    rng = np.random.default_rng(b * 100 + s)
    bcx = jnp.asarray(rng.normal(size=(b, s, 3 * W)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(3, W)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(2, SLOTS, RING, W)), jnp.float32)
    args = (bcx, taps, state, 1, jnp.asarray(rings, jnp.int32),
            jnp.asarray(starts, jnp.int32), jnp.asarray(lengths, jnp.int32))
    y0, s0 = short_conv_reference(*args)
    y1, s1 = short_conv(*args, consecutive=consecutive, use_pallas=True,
                        interpret=True)
    fed = np.arange(s)[None, :] < np.asarray(lengths)[:, None]
    np.testing.assert_allclose(np.asarray(y1)[fed], np.asarray(y0)[fed],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))
    # layer 0 and the slots no row is given stay as they were
    np.testing.assert_array_equal(np.asarray(s1[0]), np.asarray(state[0]))
    others = sorted(set(range(SLOTS)) - set(rings))
    np.testing.assert_array_equal(np.asarray(s1[1, others]),
                                  np.asarray(state[1, others]))


@pytest.mark.parametrize("cuts", [(1,), (2,), (3, 4), (16,), (5, 6, 7, 30)])
def test_a_row_fed_in_pieces_is_the_whole_rows_convolution(cuts):
    """One sequence of 45 positions fed a piece at a time through slot 3
    of a ring of 16 (pieces of 1 to 24 rows): every piece's output is the
    convolution over the whole row, whose past before 0 is nought even
    where the ring held other numbers."""
    rng = np.random.default_rng(sum(cuts))
    t = 45
    bcx = jnp.asarray(rng.normal(size=(1, t, 3 * W)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(3, W)), jnp.float32)
    want = np.asarray(causal_short_conv(bcx, taps))[0]
    state = jnp.asarray(rng.normal(size=(1, SLOTS, RING, W)), jnp.float32)
    edges = sorted(set(cuts) | {0, t} | set(range(0, t, 24)))
    for a, b in zip(edges, edges[1:]):
        y, state = short_conv_reference(
            bcx[:, a:b], taps, state, 0, jnp.asarray([3], jnp.int32),
            jnp.asarray([a], jnp.int32), jnp.asarray([b - a], jnp.int32))
        np.testing.assert_allclose(np.asarray(y)[0], want[a:b], atol=1e-5,
                                   rtol=1e-5)
