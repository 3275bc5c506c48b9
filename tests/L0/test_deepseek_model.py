"""``models.deepseek``: latent attention, rotary on interleaved pairs,
the routed experts without dropped tokens, at a tiny size in float32
(so that no rounding flips a route) against the plain reference the
benchmark keeps (``benchmarks/reference/deepseek_v3.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.models import deepseek
from apex_tpu.ops.grouped_matmul import grouped_matmul
from apex_tpu.serving.kv_cache import CacheView, KVCacheConfig, init_kv_cache
from benchmarks.harness import weights
from benchmarks.reference import deepseek_v3 as ref

SIZES = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    first_k_dense_replace=1, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=16, v_head_dim=16, max_position_embeddings=256,
    rope_theta=1e6, rope_interleave=True, rms_norm_eps=1e-6,
    routed_scaling_factor=2.448, norm_topk_prob=True)
REF_SIZES = dict(SIZES, assumed={"initializer_range": 0.02},
                 reference_longest_row=64)
CFG = models.DeepseekV3Config(**SIZES)
HIGHEST = jax.lax.Precision.HIGHEST


def make_params(seed, bias=False, std=0.2):
    """Seeded float32 weights under the reference's table; ``bias``: a
    selection bias that is not nought."""
    params = weights.make_params(ref.param_table(REF_SIZES), seed,
                                 jnp.float32, std)
    if bias:
        rng = np.random.default_rng(seed)
        for i in (1, 2):
            params[f"block_{i}"]["moe"]["e_score_correction_bias"] = \
                jnp.asarray(rng.normal(size=8) * 0.3, jnp.float32)
    return params


def ids_of(seed, rows, t):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 512, (rows, t)), jnp.int32)


def test_the_modules_parameters_are_the_references_table():
    model = CFG.build_model()
    got = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    want = make_params(1)
    assert jax.tree.map(lambda a: a.shape, got) \
        == jax.tree.map(lambda a: a.shape, want)


@pytest.mark.parametrize("seed,bias", [(1, False), (2, True), (3, True)])
def test_full_forward_matches_the_reference_on_logits(seed, bias):
    params, ids = make_params(seed, bias), ids_of(seed, 2, 48)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(CFG.build_model().apply)({"params": params}, ids)
    want = jax.jit(lambda p, i: ref.logits(p, i, REF_SIZES))(params, ids)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    # and the reference's blockwise reductions are its whole logits'
    best, gap, arg = ref.token_gaps(params, ids, None, REF_SIZES)
    lg = want[:, :-1]
    np.testing.assert_allclose(np.asarray(best), np.asarray(lg.max(-1)),
                               rtol=1e-6)
    assert (np.asarray(arg) == np.asarray(lg.argmax(-1))).all()
    nxt = np.take_along_axis(np.asarray(lg), np.asarray(ids)[:, 1:, None],
                             -1)[..., 0]
    np.testing.assert_allclose(np.asarray(gap), np.asarray(best) - nxt,
                               atol=1e-5)


WIDE = dict(SIZES, kv_lora_rank=128, num_hidden_layers=2)   # a lane tile


def _view(cfg, table):
    """A view of a fresh float32 latent pool: one sequence from
    position 0, 24 rows fed over three blocks of 8."""
    row = cfg.cache_row()
    cache_cfg = KVCacheConfig(num_layers=cfg.num_hidden_layers,
                              num_heads=row.groups,
                              head_dim=row.group_width // 2, num_blocks=5,
                              block_size=8, dtype=jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    slots = (8 + jnp.arange(24, dtype=jnp.int32))[None]
    return CacheView(init_kv_cache(cache_cfg), tables,
                     jnp.zeros((1,), jnp.int32), slots, block_size=8,
                     row=row, table=table)


@pytest.mark.parametrize("sizes,table", [(SIZES, False), (WIDE, False),
                                         (WIDE, True)],
                         ids=["gathered", "gathered_wide",
                              "table_interpret"])
def test_absorbed_attention_agrees_with_expanded(sizes, table):
    """With a cache view the model attends in the latent space (absorbed
    queries, context expanded afterwards); without one it expands keys
    and values: the same logits to rounding (a later layer's come
    from the rows an earlier one wrote), the rows in their slots and
    the lane padding left zero.  The table path (the Pallas kernel,
    interpreted) wants a value of whole lane tiles."""
    cfg = models.DeepseekV3Config(**sizes)
    ref_sizes = dict(REF_SIZES, **sizes)
    params = weights.make_params(ref.param_table(ref_sizes), 4,
                                 jnp.float32, 0.2)
    ids = ids_of(4, 1, 24)
    apply = jax.jit(cfg.build_model().apply,
                    static_argnames=("return_kv",))
    with jax.default_matmul_precision("highest"):
        want = apply({"params": params}, ids)
        got, view = apply({"params": params}, ids,
                          cache_views=_view(cfg, table), return_kv=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    pool = np.asarray(view.cache["kv"])
    used, width = cfg.cache_row().used, cfg.cache_row().width
    assert pool.shape == (cfg.num_hidden_layers, 40, width)
    for layer in range(cfg.num_hidden_layers):
        assert pool[layer, 8:32, :used].all(axis=-1).all()
        assert not pool[layer, 8:32, used:].any()
        assert not pool[layer, :8].any() and not pool[layer, 32:].any()


def test_rotary_on_interleaved_pairs_by_hand():
    """Four values, two pairs (a0, b0), (a1, b1) at position 3: pair i
    turns by 3 * theta^(-2i/4), and the result lies in the half-split
    order [a0', a1', b0', b1']."""
    a0, b0, a1, b1 = 1.0, 2.0, -0.5, 0.25
    x = jnp.asarray([[a0, b0, a1, b1]], jnp.float32)
    pos = jnp.asarray([3], jnp.int32)
    theta = 100.0
    w0, w1 = 3.0, 3.0 * theta ** -0.5
    want = [a0 * np.cos(w0) - b0 * np.sin(w0),
            a1 * np.cos(w1) - b1 * np.sin(w1),
            b0 * np.cos(w0) + a0 * np.sin(w0),
            b1 * np.cos(w1) + a1 * np.sin(w1)]
    cos, sin = deepseek.rotary_angles(pos, 4, theta)
    got = deepseek.apply_rotary(x, cos, sin, interleave=True)
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ref.rotary_interleaved(x, pos, theta))[0], want,
        rtol=1e-6)
    # not interleaved: the pairs are (x0, x2), (x1, x3)
    half = deepseek.apply_rotary(x, cos, sin, interleave=False)
    assert np.asarray(half)[0, 0] == pytest.approx(
        a0 * np.cos(w0) - a1 * np.sin(w0))


def test_the_selection_bias_selects_and_does_not_weigh():
    scores = jax.nn.sigmoid(jnp.asarray(
        np.random.default_rng(5).normal(size=(16, 8)), jnp.float32))
    none = jnp.zeros((8,), jnp.float32)
    bias = none.at[6].set(10.0)              # expert 6 is always chosen
    chosen0, w0 = deepseek.route(scores, none, 2, 2.448, True)
    chosen1, w1 = deepseek.route(scores, bias, 2, 2.448, True)
    assert (np.asarray(chosen1)[:, 0] == 6).all()
    assert (np.asarray(chosen0) != np.asarray(chosen1)).any()
    # the weights are the chosen experts' own scores, normalised and
    # scaled: the bias is nowhere in them
    picked = np.take_along_axis(np.asarray(scores), np.asarray(chosen1), 1)
    np.testing.assert_allclose(
        np.asarray(w1), picked / picked.sum(1, keepdims=True) * 2.448,
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w1).sum(1), 2.448, rtol=1e-6)
    assert float(np.asarray(w1).max()) < 2.448    # no 10 leaked in


def _moe(params, x, cfg=CFG, live=None):
    return deepseek.DeepseekV3MoE(cfg).apply(
        {"params": params["block_1"]["moe"]}, x, live)


def _reference_moe(params, x):
    return ref.experts(x, params["block_1"]["moe"], REF_SIZES,
                       lambda a: a)[0]


@pytest.mark.parametrize("one_expert", [False, True])
def test_no_token_is_dropped(one_expert):
    """Every (token, expert) pair is computed, also where the router
    sends every token to the same experts: the rows routed sum to
    T * k, and the layer is the reference's masked loop over every
    expert."""
    params = make_params(6, True)
    if one_expert:
        moe = params["block_1"]["moe"]
        moe["e_score_correction_bias"] = jnp.zeros((8,)).at[
            jnp.asarray([3, 5])].set(10.0)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 20, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, sizes = _moe(params, x)
    assert int(sizes.sum()) == 2 * 20 * 2
    if one_expert:
        assert np.asarray(sizes).tolist() == [0, 0, 0, 40, 0, 40, 0, 0]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_reference_moe(params, x)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("sizes", [[10, 0, 30, 5, 0, 0, 20, 11],
                                   [76, 0, 0, 0, 0, 0, 0, 0],
                                   [1, 1, 1, 1, 1, 1, 1, 1],
                                   [0, 0, 0, 0, 0, 0, 0, 0]])
def test_the_grouped_product_against_the_masked_dense_loop(sizes):
    """The Pallas kernel (interpreted) and the plain-loop oracle against a
    loop over the groups; rows past the last group come back nought."""
    rng = np.random.default_rng(7)
    lhs = jnp.asarray(rng.normal(size=(80, 64)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(8, 64, 128)), jnp.float32)
    want = np.zeros((80, 128), np.float32)
    start = 0
    for g, n in enumerate(sizes):
        want[start:start + n] = np.asarray(jnp.dot(
            lhs[start:start + n], rhs[g], precision=HIGHEST))
        start += n
    gs = jnp.asarray(sizes, jnp.int32)
    with jax.default_matmul_precision("highest"):
        for kw in (dict(use_pallas=True, interpret=True),
                   dict(use_pallas=False)):
            got = grouped_matmul(lhs, rhs, gs, **kw)
            np.testing.assert_allclose(np.asarray(got), want, atol=1e-4,
                                       rtol=1e-4)


def test_the_experts_held_as_two_halves_add_up_to_the_whole_layer():
    """The share test of the ``model-configs`` guide: two layers that
    each hold four of the eight experts route over all eight and compute
    their own experts' part; with the shared expert, which both compute
    alike, counted once, the parts are the uncut reference's layer."""
    params = make_params(8, True)
    x = jnp.asarray(np.random.default_rng(8).normal(size=(1, 24, 64)),
                    jnp.float32)
    moe = params["block_1"]["moe"]
    parts, counts = [], []
    with jax.default_matmul_precision("highest"):
        for first in (0, 4):
            held = dict(moe, **{k: moe[k][first:first + 4] for k in (
                "experts_gate_proj", "experts_up_proj",
                "experts_down_proj")})
            cfg = dataclasses.replace(CFG, experts_held=(first, 4))
            y, sizes = _moe({"block_1": {"moe": held}}, x, cfg)
            parts.append(np.asarray(y))
            counts.append(np.asarray(sizes))
        shared = np.asarray(ref.gated(x, moe["shared_experts"],
                                      lambda a: a))
        whole, all_sizes = _moe(params, x)
    np.testing.assert_allclose(parts[0] + parts[1] - shared,
                               np.asarray(_reference_moe(params, x)),
                               atol=2e-5, rtol=2e-5)
    assert np.concatenate(counts).tolist() == np.asarray(all_sizes).tolist()
    assert int(np.concatenate(counts).sum()) == 24 * 2
    with pytest.raises(ValueError, match="no range of the 8 routed"):
        dataclasses.replace(CFG, experts_held=(6, 4))


def test_rows_that_are_no_tokens_are_routed_nowhere():
    params = make_params(9)
    x = jnp.asarray(np.random.default_rng(9).normal(size=(2, 6, 64)),
                    jnp.float32)
    live = jnp.asarray([[1, 1, 1, 1, 0, 0], [1, 0, 0, 0, 0, 0]], bool)
    got, sizes = _moe(params, x, live=live)
    want, all_sizes = _moe(params, x)
    assert int(sizes.sum()) == 5 * 2 and int(all_sizes.sum()) == 12 * 2
    np.testing.assert_allclose(np.asarray(got)[0, :4],
                               np.asarray(want)[0, :4], atol=1e-6)


def test_what_the_family_tells_the_engine():
    row = CFG.cache_row()
    assert (row.kind, row.groups, row.heads_per_group) == ("latent", 1, 4)
    assert (row.used, row.width, row.value) == (48, 128, (0, 32))
    full = models.DeepseekV3Config().cache_row()
    assert (full.used, full.width, full.value, full.heads) \
        == (576, 640, (0, 512), 32)
    assert CFG.serving_counters() == {"routed": (2, 8)}
    gpt = models.GPTConfig().cache_row()
    assert (gpt.kind, gpt.groups, gpt.group_width, gpt.value,
            gpt.heads_per_group) == ("kv", 12, 128, (64, 128), 1)
    assert isinstance(models.GPTConfig().build_model(),
                      models.GPTLMHeadModel)
    with pytest.raises(NotImplementedError, match="no heads to scale"):
        CFG.build_model(kv_quant=True)


def _share_differing(a, b):
    """The share of (layer, token) choices that are not the same set."""
    a, b = np.sort(np.asarray(a), -1), np.sort(np.asarray(b), -1)
    return float((a != b).any(-1).mean())


def test_how_often_a_precision_chooses_other_experts_is_bounded():
    """A router's k-th and next scores lie within rounding now and then,
    and there bfloat16 arithmetic chooses another expert than float32,
    which moves a logit far more than rounding does anywhere else
    (``PERF.md`` section 2: why this family's ``served_gap_max`` reads
    near 1 where GPT-2's reads 0.05).  The program in bfloat16 differs
    from the float32 reference in a small share of its (token, layer)
    choices, about as the reference's own bfloat16 control does, and
    fp8 in several times as many."""
    params, ids = make_params(10, True), ids_of(10, 2, 96)
    want = ref.routing_choices(params, ids, REF_SIZES)
    assert want.shape == (2, 2, 96, 2)
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    _, state = jax.jit(lambda p, i: CFG.build_model().apply(
        {"params": p}, i, mutable=["intermediates"]))(half, ids)
    got = jnp.stack([state["intermediates"][f"block_{i}"]["moe"]["chosen"][0]
                     for i in (1, 2)])
    program = _share_differing(got, want)
    low = {p: _share_differing(
        ref.routing_choices(params, ids, REF_SIZES, p), want)
        for p in ("bfloat16", "fp8")}
    print("share of choices that differ:", program, low)
    assert program < 0.08 and low["bfloat16"] < 0.08
    assert low["fp8"] > 3 * max(program, low["bfloat16"], 0.02)
    # in float32 the program chooses as the reference does
    _, state = jax.jit(lambda p, i: CFG.build_model().apply(
        {"params": p}, i, mutable=["intermediates"]))(params, ids)
    same = jnp.stack([state["intermediates"][f"block_{i}"]["moe"]["chosen"][0]
                      for i in (1, 2)])
    assert _share_differing(same, want) == 0.0
