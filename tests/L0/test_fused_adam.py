"""FusedAdam parity tests (reference tests/L0/run_mixed_adam/test_mixed_adam.py).

Oracles: (1) an exact numpy replica of the reference CUDA kernel math
(``fused_adam_cuda_kernel.cu:48-84``), tight tolerance; (2) optax.adam,
loose tolerance (formulation differs by an eps-scale term, same as the
reference's FusedAdam-vs-torch.optim.Adam comparison).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from apex_tpu.optimizers import FusedAdam, FP16_Optimizer


def numpy_apex_adam(p, m, v, g, lr, beta1, beta2, eps, step, scale=1.0,
                    wd=0.0, eps_inside=False, bias_correction=True):
    g = g / scale
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * g * g
    denom = np.sqrt(v + eps) if eps_inside else np.sqrt(v) + eps
    if bias_correction:
        step_size = lr * np.sqrt(1 - beta2 ** step) / (1 - beta1 ** step)
    else:
        step_size = lr
    p = p - step_size * (m / denom + wd * p)
    return p, m, v


def params_tree(seed=0, n=1000):
    rng = np.random.RandomState(seed)
    return {"w": jnp.asarray(rng.randn(37, 13), jnp.float32),
            "b": jnp.asarray(rng.randn(n), jnp.float32)}


@pytest.mark.parametrize("eps_inside", [False, True])
@pytest.mark.parametrize("wd", [0.0, 0.01])
def test_matches_numpy_reference(eps_inside, wd):
    params = params_tree()
    opt = FusedAdam(lr=1e-2, eps_inside_sqrt=eps_inside, weight_decay=wd,
                    use_pallas=False)
    state = opt.init(params)
    rng = np.random.RandomState(1)

    np_p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    np_m = {k: np.zeros_like(v) for k, v in np_p.items()}
    np_v = {k: np.zeros_like(v) for k, v in np_p.items()}

    for step in range(1, 4):
        grads = {k: jnp.asarray(rng.randn(*np.shape(v)), jnp.float32)
                 for k, v in params.items()}
        params, state = opt.step(params, grads, state)
        for k in np_p:
            np_p[k], np_m[k], np_v[k] = numpy_apex_adam(
                np_p[k], np_m[k], np_v[k], np.asarray(grads[k], np.float64),
                1e-2, 0.9, 0.999, 1e-8, step, wd=wd, eps_inside=eps_inside)
    for k in np_p:
        np.testing.assert_allclose(np.asarray(params[k]), np_p[k],
                                   rtol=1e-5, atol=1e-6)


def test_close_to_optax_adam():
    params = params_tree()
    opt = FusedAdam(lr=1e-3, use_pallas=False)
    state = opt.init(params)
    ox = optax.adam(1e-3)
    ox_state = ox.init(params)
    ox_params = params
    rng = np.random.RandomState(2)
    for _ in range(5):
        grads = {k: jnp.asarray(rng.randn(*np.shape(v)), jnp.float32)
                 for k, v in params.items()}
        params, state = opt.step(params, grads, state)
        upd, ox_state = ox.update(grads, ox_state, ox_params)
        ox_params = optax.apply_updates(ox_params, upd)
    for k in params:
        np.testing.assert_allclose(np.asarray(params[k]),
                                   np.asarray(ox_params[k]),
                                   rtol=1e-3, atol=1e-5)


def test_pallas_interpret_matches_jnp():
    """Fused (Pallas) vs pure-jnp within tight tolerance — the TPU version
    of the reference's L1 'with/without extensions' parity gate (bitwise is
    only required between interpret and compiled runs of the *same* kernel;
    differently-fused XLA programs legitimately differ in the last ulp)."""
    params = params_tree(n=5000)
    grads = {k: jnp.asarray(np.random.RandomState(3).randn(*np.shape(v)),
                            jnp.float32) for k, v in params.items()}
    outs = {}
    for use_pallas in (False, True):
        opt = FusedAdam(lr=1e-2, weight_decay=0.01, use_pallas=use_pallas)
        state = opt.init(params)
        p, state = opt.step(params, grads, state)
        p, state = opt.step(p, grads, state)
        outs[use_pallas] = p
    for k in params:
        np.testing.assert_allclose(np.asarray(outs[False][k]),
                                   np.asarray(outs[True][k]),
                                   rtol=1e-4, atol=1e-6)


def test_scale_divides_grads():
    params = params_tree()
    grads = {k: jnp.ones_like(v) * 8.0 for k, v in params.items()}
    opt = FusedAdam(lr=1e-2, use_pallas=False)
    s1 = opt.init(params)
    p_scaled, _ = opt.step(params, grads, s1, scale=8.0)
    s2 = opt.init(params)
    unit = {k: jnp.ones_like(v) for k, v in params.items()}
    p_unit, _ = opt.step(params, unit, s2, scale=1.0)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_scaled[k]),
                                   np.asarray(p_unit[k]), rtol=1e-6)


def test_max_grad_norm_clips():
    """Clipping folds into combined_scale: a step with max_grad_norm=M on
    grads of norm N>M must equal a step with scale=N/M and no clipping
    (reference fused_adam.py:98-104)."""
    params = {"w": jnp.ones((4,), jnp.float32)}
    grads = {"w": jnp.full((4,), 100.0)}  # norm 200
    opt = FusedAdam(lr=0.1, bias_correction=False, max_grad_norm=1.0,
                    use_pallas=False)
    state = opt.init(params)
    p_clip, _ = opt.step(params, grads, state)

    opt2 = FusedAdam(lr=0.1, bias_correction=False, use_pallas=False)
    st2 = opt2.init(params)
    p_scaled, _ = opt2.step(params, grads, st2, scale=200.0)
    np.testing.assert_allclose(np.asarray(p_clip["w"]),
                               np.asarray(p_scaled["w"]), rtol=1e-6)

    # norm below the threshold: no clipping, matches scale=1
    small = {"w": jnp.full((4,), 0.001)}
    st3 = opt.init(params)
    p3, _ = opt.step(params, small, st3)
    st4 = opt2.init(params)
    p4, _ = opt2.step(params, small, st4)
    np.testing.assert_allclose(np.asarray(p3["w"]), np.asarray(p4["w"]),
                               rtol=1e-6)


def test_amsgrad_rejected():
    with pytest.raises(RuntimeError, match="AMSGrad"):
        FusedAdam(amsgrad=True)


def test_output_params_dtype():
    params = params_tree()
    grads = {k: jnp.ones_like(v) for k, v in params.items()}
    opt = FusedAdam(use_pallas=False)
    state = opt.init(params)
    p_half, _ = opt.step(params, grads, state,
                         output_params_dtype=jnp.bfloat16)
    assert all(v.dtype == jnp.bfloat16
               for v in jax.tree_util.tree_leaves(p_half))


def test_optax_protocol_with_amp():
    """FusedAdam slots into amp.initialize as the inner optimizer."""
    import flax.linen as nn
    from apex_tpu import amp

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4)(x)

    model, optimizer = amp.initialize(Tiny(), FusedAdam(lr=0.05,
                                                        use_pallas=False),
                                      opt_level="O2", verbosity=0)
    params = model.init(jax.random.PRNGKey(0), jnp.ones((2, 8)))
    opt_state = optimizer.init(params)

    @jax.jit
    def step(params, opt_state, x, y):
        def loss_fn(p):
            out = model.apply(p, x).astype(jnp.float32)
            return amp.scale(jnp.mean((out - y) ** 2), opt_state)
        grads = jax.grad(loss_fn)(params)
        return optimizer.step(params, grads, opt_state)

    x = jnp.ones((2, 8))
    y = jnp.ones((2, 4))
    losses = []
    for _ in range(10):
        params, opt_state = step(params, opt_state, x, y)
        out = model.apply(params, x).astype(jnp.float32)
        losses.append(float(jnp.mean((out - y) ** 2)))
    assert losses[-1] < losses[0]


def test_fp16_optimizer_protocol():
    """FP16_Optimizer: half params, flat fp32 masters, overflow skip."""
    half = {"w": jnp.ones((8, 8), jnp.bfloat16),
            "b": jnp.zeros((8,), jnp.bfloat16)}
    fp16_opt = FP16_Optimizer(FusedAdam(lr=0.1, use_pallas=False),
                              dynamic_loss_scale=True)
    state = fp16_opt.init(half)
    assert state.master.dtype == jnp.float32
    scale0 = float(fp16_opt.loss_scale(state))

    grads = {"w": jnp.full((8, 8), scale0, jnp.bfloat16),
             "b": jnp.full((8,), scale0, jnp.bfloat16)}
    new_half, state = fp16_opt.step(half, grads, state)
    assert new_half["w"].dtype == jnp.bfloat16
    assert not np.allclose(np.asarray(new_half["w"], np.float32), 1.0)

    bad = {"w": grads["w"].at[0, 0].set(jnp.inf), "b": grads["b"]}
    frozen, state = fp16_opt.step(new_half, bad, state)
    np.testing.assert_array_equal(np.asarray(frozen["w"], np.float32),
                                  np.asarray(new_half["w"], np.float32))
    assert float(fp16_opt.loss_scale(state)) == scale0 / 2


@pytest.mark.parametrize("use_pallas", [False, True])
def test_in_kernel_skip_step(use_pallas):
    """skip=True must be a full no-op — params, m, v AND the
    bias-correction step clock unchanged (the reference's patched step
    is a one-shot no-op on overflow, amp/handle.py:130-150) — with the
    select fused inside the kernel, even when the grads carry inf."""
    params = params_tree()
    grads = {k: jnp.ones_like(v) for k, v in params.items()}
    bad = {k: jnp.full_like(v, jnp.inf) for k, v in params.items()}
    opt = FusedAdam(lr=1e-2, weight_decay=0.01, use_pallas=use_pallas)
    state = opt.init(params)

    p_skip, s_skip = opt.step(params, bad, state,
                              skip=jnp.asarray(True))
    for k in params:
        np.testing.assert_array_equal(np.asarray(p_skip[k]),
                                      np.asarray(params[k]))
    np.testing.assert_array_equal(np.asarray(s_skip.m), np.asarray(state.m))
    np.testing.assert_array_equal(np.asarray(s_skip.v), np.asarray(state.v))
    assert int(s_skip.step) == int(state.step)

    # skip=False must match the no-skip-arg step exactly
    p_a, s_a = opt.step(params, grads, state, skip=jnp.asarray(False))
    p_b, s_b = opt.step(params, grads, state)
    for k in params:
        np.testing.assert_array_equal(np.asarray(p_a[k]), np.asarray(p_b[k]))
    np.testing.assert_array_equal(np.asarray(s_a.m), np.asarray(s_b.m))
    assert int(s_a.step) == int(s_b.step) == 1

    # a skipped first step then a real one == just the real one (the
    # clock advanced once; numerics identical)
    p_c, s_c = opt.step(p_skip, grads, s_skip, skip=jnp.asarray(False))
    for k in params:
        np.testing.assert_allclose(np.asarray(p_c[k]), np.asarray(p_b[k]),
                                   rtol=1e-6, atol=1e-7)
    assert int(s_c.step) == 1


def test_amp_optimizer_fused_skip_path():
    """AmpOptimizer.apply_gradients routes FusedAdam through the
    in-kernel skip (supports_fused_skip) — same trajectory as the
    generic tree-select path, and overflow still skips + halves the
    scale."""
    from apex_tpu.amp.optimizer import AmpOptimizer
    from apex_tpu.amp.scaler import LossScaler

    params = params_tree()
    inner = FusedAdam(lr=1e-2, use_pallas=False)
    amp_opt = AmpOptimizer(inner, LossScaler(init_scale=2.0 ** 8))
    state = amp_opt.init(params)
    assert inner.supports_fused_skip

    scale0 = float(amp_opt.loss_scale(state))
    good = {k: jnp.ones_like(v) * scale0 for k, v in params.items()}
    p1, s1 = amp_opt.step(params, good, state)
    assert int(s1.applied_steps) == 1 and int(s1.skipped_steps) == 0
    assert not np.allclose(np.asarray(p1["w"]), np.asarray(params["w"]))

    bad = {k: jnp.full_like(v, jnp.inf) for k, v in params.items()}
    p2, s2 = amp_opt.step(p1, bad, s1)
    for k in params:
        np.testing.assert_array_equal(np.asarray(p2[k]), np.asarray(p1[k]))
    assert int(s2.skipped_steps) == 1
    assert float(amp_opt.loss_scale(s2)) == scale0 / 2
    np.testing.assert_array_equal(np.asarray(s2.inner.m),
                                  np.asarray(s1.inner.m))


def test_tree_layout_matches_flat():
    """layout='tree' (per-leaf fused update) walks the same trajectory
    as the flat-buffer layout — same math, only the memory layout and
    fusion structure differ (BENCH_NOTES: the tree layout skips the
    per-step concat/pad/slice-back HBM traffic)."""
    params = params_tree(n=5000)
    rng = np.random.RandomState(7)
    grads = [{k: jnp.asarray(rng.randn(*np.shape(v)), jnp.float32)
              for k, v in params.items()} for _ in range(3)]
    outs = {}
    for layout in ("flat", "tree"):
        opt = FusedAdam(lr=1e-2, weight_decay=0.01, use_pallas=False,
                        layout=layout)
        state = opt.init(params)
        p = params
        for g in grads:
            p, state = jax.jit(opt.step)(p, g, state, scale=2.0)
        outs[layout] = (p, state)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(outs["tree"][0][k]), np.asarray(outs["flat"][0][k]),
            rtol=1e-6, atol=1e-7)
    assert int(outs["tree"][1].step) == int(outs["flat"][1].step) == 3
    # tree state mirrors the params structure
    assert set(outs["tree"][1].m.keys()) == set(params.keys())


def test_tree_layout_param_groups_and_max_grad_norm():
    """Per-group lr/wd/max_grad_norm resolve identically in both
    layouts (group-wise grad-norm clipping included)."""
    params = {"w": jnp.ones((8, 8)) * 0.3, "bias": jnp.ones((8,)) * 0.1,
              "u": jnp.ones((4, 4))}
    grads = {"w": jnp.ones((8, 8)) * 3.0, "bias": jnp.ones((8,)) * 3.0,
             "u": jnp.ones((4, 4)) * 3.0}
    groups = [{"match": r"bias", "weight_decay": 0.0, "lr": 1e-3},
              {"match": r"u", "max_grad_norm": 0.5}]
    outs = {}
    for layout in ("flat", "tree"):
        opt = FusedAdam(lr=1e-2, weight_decay=0.1, use_pallas=False,
                        param_groups=groups, layout=layout)
        state = opt.init(params)
        p, state = opt.step(params, grads, state)
        p, state = opt.step(p, grads, state)
        outs[layout] = p
    for k in params:
        np.testing.assert_allclose(np.asarray(outs["tree"][k]),
                                   np.asarray(outs["flat"][k]),
                                   rtol=1e-6, atol=1e-7)


def test_tree_layout_skip_step():
    params = params_tree()
    bad = {k: jnp.full_like(v, jnp.inf) for k, v in params.items()}
    good = {k: jnp.ones_like(v) for k, v in params.items()}
    opt = FusedAdam(lr=1e-2, layout="tree", use_pallas=False)
    state = opt.init(params)
    p_skip, s_skip = opt.step(params, bad, state, skip=jnp.asarray(True))
    for k in params:
        np.testing.assert_array_equal(np.asarray(p_skip[k]),
                                      np.asarray(params[k]))
    np.testing.assert_array_equal(np.asarray(s_skip.m["w"]),
                                  np.asarray(state.m["w"]))
    assert int(s_skip.step) == 0
    # and the fused-skip path through AmpOptimizer works for tree too
    from apex_tpu.amp.optimizer import AmpOptimizer
    from apex_tpu.amp.scaler import LossScaler
    amp_opt = AmpOptimizer(opt, LossScaler(init_scale=4.0))
    astate = amp_opt.init(params)
    p1, a1 = amp_opt.step(params, {k: v * 4.0 for k, v in good.items()},
                          astate)
    assert int(a1.applied_steps) == 1
    p2, a2 = amp_opt.step(p1, bad, a1)
    for k in params:
        np.testing.assert_array_equal(np.asarray(p2[k]), np.asarray(p1[k]))
    assert int(a2.skipped_steps) == 1


def test_tree_layout_add_param_group():
    """Mid-training group addition carries per-leaf moments over and
    zero-inits new leaves (the reference's unfreeze use case)."""
    params = params_tree()
    grads = {k: jnp.ones_like(v) * 0.1 for k, v in params.items()}
    opt = FusedAdam(lr=1e-2, layout="tree", use_pallas=False)
    state = opt.init(params)
    p, state = opt.step(params, grads, state)

    bigger = dict(p, extra=jnp.zeros((5, 5)))
    opt2, state2 = opt.add_param_group(state, bigger, match=r"extra",
                                       lr=1e-4)
    np.testing.assert_array_equal(np.asarray(state2.m["w"]),
                                  np.asarray(state.m["w"]))
    np.testing.assert_array_equal(np.asarray(state2.m["extra"]),
                                  np.zeros((5, 5), np.float32))
    assert int(state2.step) == 1
    g2 = dict({k: jnp.ones_like(v) * 0.1 for k, v in p.items()},
              extra=jnp.ones((5, 5)))
    p2, state3 = opt2.step(bigger, g2, state2)
    assert p2["extra"].shape == (5, 5)
    assert not np.allclose(np.asarray(p2["extra"]), 0.0)


# -- the flat step's routes into the kernel (PR 30) -------------------------
# A buffer whose length is a multiple of 128 and fills a sublane tile
# enters ``_adam_kernel`` as its (n // 128, 128) view over a cdiv grid
# with a ragged last block; any other length is padded to whole blocks.
# Each layout below is stepped through the interpreted kernel and through
# ``use_pallas=False``.

def _tree_of(sizes, seed):
    rng = np.random.RandomState(seed)
    return {f"p{i}": jnp.asarray(rng.randn(*s), jnp.float32)
            for i, s in enumerate(sizes)}


def _two_device_zero(opt):
    from jax.sharding import Mesh
    return opt.with_zero(Mesh(np.asarray(jax.devices()[:2]), ("data",)))


# name -> (leaf shapes, FusedAdam keywords, wrap, buffer length, the
# lengths the kernel is called on)
FLAT_ROUTES = {
    # one whole block of 512 x 128
    "whole_blocks": ([(256, 128), (256, 128)], {}, None, 65536, [65536]),
    # 700 rows of 128: the second block holds 188
    "ragged_last_block": ([(300, 128), (400, 128)], {}, None, 89600,
                          [89600]),
    # 12 rows of 128: shorter than one block, the array is the block
    "shorter_than_a_block": ([(12, 128)], {}, None, 1536, [1536]),
    # 1,037 elements: the padded route
    "not_a_multiple_of_128": ([(37, 13), (556,)], {"pad_to": 1}, None, 1037,
                              [1037]),
    # group 0 is p1 (1,000 elements), group 1 is p0 (300) and p2 (1,536):
    # slices at 0 and 1,000, neither length a multiple of 128
    "unaligned_group_bounds": (
        [(300,), (1000,), (12, 128)],
        {"param_groups": [{"match": lambda path: "p1" in path,
                           "lr": 3e-2, "weight_decay": 0.0}]},
        None, 2944, [1000, 1836]),
    # 2 shards of 350 rows each: the ragged grid inside shard_map
    "with_zero_2_devices": ([(300, 128), (400, 128)], {}, _two_device_zero,
                            89600, [44800]),
}


@pytest.mark.parametrize("skip", [False, True], ids=["step", "skip"])
@pytest.mark.parametrize("route", list(FLAT_ROUTES))
def test_flat_routes_match_jnp(route, skip, monkeypatch):
    """The Pallas route (interpreted) against ``use_pallas=False`` on
    parameters, ``m``, ``v`` and ``step``, to the tolerance
    ``test_pallas_interpret_matches_jnp`` holds; a skipped step leaves
    every element, the ragged tail included, exactly as it was."""
    import apex_tpu.optimizers.fused_adam as fa
    sizes, kw, wrap, buf_len, kernel_lens = FLAT_ROUTES[route]
    params, grads = _tree_of(sizes, 0), _tree_of(sizes, 1)
    bad = jax.tree.map(lambda x: jnp.full_like(x, jnp.inf), grads)

    seen = []
    real = fa._adam_flat_pallas

    def spy(p, *a, **k):
        seen.append(p.shape[0])
        return real(p, *a, **k)

    monkeypatch.setattr(fa, "_adam_flat_pallas", spy)

    outs = {}
    for use_pallas in (False, True):
        opt = FusedAdam(lr=1e-2, weight_decay=0.01, use_pallas=use_pallas,
                        **kw)
        if wrap is not None:
            opt = wrap(opt)
        state = opt.init(params)
        assert state.m.shape == (buf_len,)
        # a real step first, so that a skipped one has moments to keep
        p, state = opt.step(params, grads, state)
        before = (p, state)
        p, state = opt.step(p, bad if skip else grads, state,
                            skip=jnp.asarray(skip))
        outs[use_pallas] = (p, state)
        if skip:
            for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(
                    (p, state))):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert int(state.step) == 1
        else:
            assert int(state.step) == 2
    assert sorted(set(seen)) == sorted(kernel_lens)
    for a, b in zip(jax.tree.leaves(outs[False]), jax.tree.leaves(
            outs[True])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("rows_of_128", [700, 12])
def test_view_route_is_the_padded_route_bit_for_bit(rows_of_128):
    """The same elements through the view (ragged last block, or the
    array as its own block) and, one element longer, through the padded
    route: elementwise work, so every shared element is the same bits."""
    from apex_tpu.optimizers.fused_adam import _adam_flat_pallas
    n = rows_of_128 * 128
    rng = np.random.RandomState(5)
    p, m, g = (jnp.asarray(rng.randn(n + 1), jnp.float32) for _ in range(3))
    v = jnp.asarray(rng.rand(n + 1), jnp.float32)
    scalars = jnp.asarray([1e-2, 0.9, 0.999, 1e-8, 2.0, 0.01, 1.0],
                          jnp.float32)
    padded = _adam_flat_pallas(p, m, v, g, scalars, eps_inside_sqrt=False,
                               interpret=True)
    view = _adam_flat_pallas(p[:n], m[:n], v[:n], g[:n], scalars,
                             eps_inside_sqrt=False, interpret=True)
    for a, b in zip(padded, view):
        assert b.shape == (n,)
        np.testing.assert_array_equal(np.asarray(a)[:n], np.asarray(b))
