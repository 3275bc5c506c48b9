"""The ``lfm2_moe`` family under ``DecodeEngine`` and ``InferenceServer``:
short-convolution layers whose state is a ring of a few rows a slot
(``serving.kv_cache``, ``"conv_state"``) beside grouped-query attention
layers in the block table, a dense layer and expert layers with no
shared expert.  Against ONE full pass of the plain reference
(``benchmarks/reference/lfm2_moe.py``).  Tiny sizes, float32 weights and
pool, on the CPU, with the published ``conv_L_cache`` of 3."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.models.family import layer_states, layer_windows
from apex_tpu.serving import InferenceServer
from apex_tpu.serving.engine import DecodeEngine
from apex_tpu.serving.kv_cache import STATE_LEAF, state_rows
from benchmarks.harness import weights
from benchmarks.reference import lfm2_moe as ref

pytestmark = pytest.mark.serving

BS, CHUNK = 4, 16
RING = 8                        # 2 rows read and a verify launch of 5
KINDS = ("conv", "full_attention", "conv", "conv", "full_attention")
SIZES = dict(
    vocab_size=211, hidden_size=64, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    num_dense_layers=1, conv_L_cache=3, routed_scaling_factor=1.0,
    norm_topk_prob=True, norm_eps=1e-5, max_position_embeddings=512)
CFG = models.Lfm2MoeConfig(**SIZES, layer_types=KINDS,
                           initializer_range=0.3)
REF_SIZES = dict(SIZES, layer_types=list(KINDS),
                 rope_parameters={"rope_theta": 1e6},
                 assumed={"initializer_range": 0.3})


@pytest.fixture(scope="module")
def params():
    p = weights.make_params(ref.param_table(REF_SIZES), 33, jnp.float32, 0.3)
    p["layer_3"]["moe"]["e_score_correction_bias"] = jnp.asarray(
        np.random.default_rng(33).normal(size=8) * 0.3, jnp.float32)
    return p


@pytest.fixture(scope="module")
def reference_logits(params):
    fn = jax.jit(lambda ids: ref.logits(params, ids, REF_SIZES))
    return lambda ids: np.asarray(fn(jnp.asarray([ids], jnp.int32)))[0]


def _engine(params, **kw):
    kw.setdefault("max_batch_size", 3)
    kw.setdefault("max_context", 128)
    kw.setdefault("block_size", BS)
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("verify_rows", 5)
    return DecodeEngine(CFG, params, **kw)


def _server(params, **kw):
    kw.setdefault("max_batch_size", 3)
    kw.setdefault("max_context", 128)
    kw.setdefault("block_size", BS)
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("prefill_chunk", CHUNK)
    return InferenceServer(CFG, params, **kw)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 211, n).tolist()


def _greedy_by_reference(reference_logits, prompt, new):
    row = list(prompt)
    for _ in range(new):
        row.append(int(np.argmax(reference_logits(row)[-1])))
    return row[len(prompt):]


def _prefill(e, prompt, blocks, slot, cuts=()):
    """``prompt`` through the chunk program, cut at ``cuts`` and then
    every ``CHUNK``; the last chunk's logits."""
    edges = sorted(set(cuts) | set(range(0, len(prompt), CHUNK))
                   | {len(prompt)})
    last = None
    for a, b in zip(edges, edges[1:]):
        last = e.chunk_prefill(prompt[a:b], a, blocks, pad_to=CHUNK,
                               slot=slot)
    return np.asarray(last)


# -- what the family tells the engine ------------------------------------------

def test_the_family_says_which_layers_keep_a_state():
    assert layer_states(CFG) == ((2, 64), None, (2, 64), (2, 64), None)
    assert layer_windows(CFG) == (None,) * 5
    row = CFG.cache_row()
    assert (row.kind, row.groups, row.group_width, row.heads_per_group) \
        == ("kv", 2, 32, 2)
    # the published model's 40 layers: 30 conv, 10 attention
    whole = models.Lfm2MoeConfig()
    assert whole.layer_types.count("conv") == 30
    assert [i for i, k in enumerate(whole.layer_types)
            if k == "full_attention"] == list(range(2, 40, 4))
    assert whole.head_dim == 64
    gpt = models.GPTConfig(vocab_size=64, hidden_size=32,
                           num_hidden_layers=3, num_attention_heads=2,
                           intermediate_size=64, max_position_embeddings=32)
    assert layer_states(gpt) == (None,) * 3
    with pytest.raises(ValueError, match="layer_types"):
        models.Lfm2MoeConfig(**dict(SIZES, num_hidden_layers=2),
                             layer_types=("conv", "sliding_attention"))
    with pytest.raises(ValueError, match="conv_bias"):
        models.Lfm2MoeConfig(**SIZES, layer_types=KINDS, conv_bias=True)


def test_the_pool_holds_a_ring_of_a_few_rows_a_slot_for_each_conv_layer(
        params):
    e = _engine(params)
    assert e.state_rows == RING == state_rows(2, 5, BS)
    assert e.max_verify_rows == RING - 2
    assert e.max_fed_rows is None
    assert e.cache[STATE_LEAF].shape == (3, 3, RING, 64)
    assert e.cache[STATE_LEAF].dtype == jnp.float32
    # the two attention layers alone keep tokens, through the table
    assert e.cache["kv"].shape[0] == 2
    assert e.layers == ((STATE_LEAF, 0, None), ("kv", 0, None),
                        (STATE_LEAF, 1, None), (STATE_LEAF, 2, None),
                        ("kv", 1, None))


# -- chunk prefill then decode through the pool ---------------------------------

@pytest.mark.parametrize("n", [1, 2, 5, 16, 17, 41])
def test_chunked_prefill_then_decode_match_the_references_full_pass(
        params, reference_logits, n):
    """Prompts of one token, of fewer than the taps, on a chunk's edge,
    a token past it and over two chunks; then six decode steps fed the
    reference's own tokens, in slot 1 of 3.  Logits at every step
    against ONE full pass of the plain reference over the whole row."""
    with jax.default_matmul_precision("highest"):
        e = _engine(params)
        prompt = _prompt(n, n)
        blocks = e.allocator.alloc(e.blocks_per_seq)
        row, got = list(prompt), [_prefill(e, prompt, blocks, 1)]
        tables = np.zeros((3, e.blocks_per_seq), np.int32)
        tables[1] = blocks
        for _ in range(6):
            row.append(int(np.argmax(got[-1])))
            logits = e.decode([0, row[-1], 0], [0, len(row) - 1, 0], tables)
            got.append(np.asarray(logits)[1])
    want = reference_logits(row)
    for i, g in enumerate(got):
        np.testing.assert_allclose(g, want[n - 1 + i], atol=3e-4, rtol=3e-4)


@pytest.mark.parametrize("cut", [1, 2, 3, 4, 5, 6])
def test_a_prompt_cut_into_chunks_anywhere_gives_one_chunks_logits(
        params, cut):
    """A prompt of 15 tokens in one chunk, and cut after ``cut`` tokens
    (every offset modulo the 3 taps, twice) and again 4 later: the
    state a chunk hands on is what the unsplit chunk computes, so the
    last logits and two decode steps after them agree."""
    prompt = _prompt(7, 15)
    outs = []
    with jax.default_matmul_precision("highest"):
        for cuts in ((), (cut, cut + 4)):
            e = _engine(params)
            blocks = e.allocator.alloc(e.blocks_per_seq)
            got = [_prefill(e, prompt, blocks, 2, cuts)]
            tables = np.zeros((3, e.blocks_per_seq), np.int32)
            tables[2] = blocks
            row = list(prompt)
            for _ in range(2):
                row.append(int(np.argmax(got[-1])))
                got.append(np.asarray(e.decode(
                    [0, 0, row[-1]], [0, 0, len(row) - 1], tables))[2])
            outs.append(got)
    for a, b in zip(*outs):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


# -- verify launches whose drafts are rejected ------------------------------------

@pytest.mark.parametrize("kept", [0, 1, 2, 3, 4])
def test_drafts_rejected_at_any_position_leave_the_state_as_it_was(
        params, reference_logits, kept):
    """Slot 0 feeds its pending token and four drafts of which the first
    ``kept`` are right; slot 2 feeds three right ones.  Then both go on
    from what they accepted with the real rows: the rejected drafts' rows
    lie at positions the next launch writes before it reads them, so the
    logits are the reference's (and no speculation's) everywhere."""
    with jax.default_matmul_precision("highest"):
        e = _engine(params)
        rows = [_prompt(41, 40), None, _prompt(42, 20)]
        fed = [23, 0, 9]
        tables = np.zeros((3, e.blocks_per_seq), np.int32)
        for i in (0, 2):
            tables[i] = e.allocator.alloc(e.blocks_per_seq)
            _prefill(e, rows[i][:fed[i]], tables[i], i)
        tokens = np.zeros((3, 5), np.int32)
        wrong = [(t + 1) % 211 for t in rows[0][24:28]]
        tokens[0] = rows[0][23:24 + kept] + wrong[kept:]
        tokens[2, :3] = rows[2][9:12]
        logits = np.asarray(e.verify(tokens, [5, 0, 3], fed, tables))
        a0 = 23 + kept + 1                  # slot 0's accepted length
        again = np.zeros((3, 5), np.int32)
        again[0] = rows[0][a0:a0 + 5]
        again[2, :2] = rows[2][12:14]
        after = np.asarray(e.verify(again, [5, 0, 2], [a0, 0, 12], tables))
        one = np.asarray(e.decode([rows[0][a0 + 5], 0, rows[2][14]],
                                  [a0 + 5, 0, 14], tables))
    want = [reference_logits(rows[0]), None, reference_logits(rows[2])]
    tol = dict(atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(logits[0, :kept + 1],
                               want[0][23:24 + kept], **tol)
    np.testing.assert_allclose(logits[2, :3], want[2][9:12], **tol)
    np.testing.assert_allclose(after[0], want[0][a0:a0 + 5], **tol)
    np.testing.assert_allclose(after[2, :2], want[2][12:14], **tol)
    np.testing.assert_allclose(one[0], want[0][a0 + 5], **tol)
    np.testing.assert_allclose(one[2], want[2][14], **tol)


# -- a slot used again, preemption ------------------------------------------------

def test_a_slot_used_again_gives_a_fresh_servers_logits(params):
    """Request A fills slot 1's ring; request B then takes slot 1 from
    position 0 with no reset: positions before 0 read as nought."""
    first, second = _prompt(81, 23), _prompt(82, 10)

    def run(e, prompt):
        blocks = e.allocator.alloc(e.blocks_per_seq)
        got = [_prefill(e, prompt, blocks, 1)]
        tables = np.zeros((3, e.blocks_per_seq), np.int32)
        tables[1] = blocks
        row = list(prompt)
        for _ in range(3):
            row.append(int(np.argmax(got[-1])))
            got.append(np.asarray(e.decode(
                [0, row[-1], 0], [0, len(row) - 1, 0], tables))[1])
        e.allocator.free(blocks)
        return got

    with jax.default_matmul_precision("highest"):
        used = _engine(params)
        run(used, first)
        assert np.any(np.asarray(used.cache[STATE_LEAF])[:, 1] != 0)
        again = run(used, second)
        fresh = run(_engine(params), second)
    for a, b in zip(again, fresh):
        np.testing.assert_array_equal(a, b)


def test_the_server_serves_the_family_with_its_defaults(
        params, reference_logits):
    """Chunked prefill, the pipelined loop and speculation as they come;
    the tokens are the reference's greedy ones; ``stats()`` reports the
    state's kind."""
    with jax.default_matmul_precision("highest"):
        srv = _server(params)
        prompts = [_prompt(51, 45), _prompt(52, 9), _prompt(53, 2)]
        outs = srv.generate(prompts, max_new_tokens=8)
    for p, o in zip(prompts, outs):
        assert o == _greedy_by_reference(reference_logits, p, 8)
    st = srv.stats()
    state = st["memory"]["by_kind"]["state"]
    assert (state["layers"], state["rows_kept"], state["rows_a_slot"],
            state["width"]) == (3, 2, RING, 64)
    assert state["bytes"] == 3 * 3 * RING * 64 * 4
    assert st["memory"]["pool_bytes"] == state["bytes"] \
        + st["memory"]["by_kind"]["full"]["bytes"]
    spec = st["speculation"]
    assert spec["decode_steps"] + spec["verify_steps"] > 0
    ex = st["experts"]
    assert ex["enabled"] and ex["layers"] == 4 and ex["experts_held"] == 8


def test_preemption_and_re_prefill_leave_the_tokens_as_they_were(params):
    """A pool too small for both requests' whole rows: one is preempted
    and prefilled again, its state made anew in whichever slot it is
    given then; the tokens are a roomy server's."""
    prompts = [_prompt(71, 50), _prompt(72, 44)]
    with jax.default_matmul_precision("highest"):
        tight = _server(params, num_blocks=29)
        got = tight.generate(prompts, max_new_tokens=14)
        roomy = _server(params)
        want = roomy.generate(prompts, max_new_tokens=14)
    assert got == want
    assert tight.stats()["preemptions"] >= 1
    assert roomy.stats()["preemptions"] == 0


def test_a_shared_prefix_is_served_as_the_reference_has_it_with_no_hit(
        params, reference_logits):
    """Two requests share 24 tokens: a hit would need the conv layers'
    state at the shared length, which the first request's ring has moved
    past, so no hit is taken; both get the reference's tokens."""
    shared = _prompt(61, 24)
    with jax.default_matmul_precision("highest"):
        srv = _server(params)
        first = srv.generate([shared + _prompt(62, 3)], max_new_tokens=4)[0]
        second = srv.generate([shared + _prompt(63, 6)],
                              max_new_tokens=4)[0]
    assert first == _greedy_by_reference(
        reference_logits, shared + _prompt(62, 3), 4)
    assert second == _greedy_by_reference(
        reference_logits, shared + _prompt(63, 6), 4)
    assert srv.prefix_cache is None
    assert srv.prefix.count("prefix_hit_tokens") == 0


# -- the expert layer with no shared expert ---------------------------------------

def test_an_expert_layer_with_no_shared_expert_is_the_references(params):
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(1, 12, 64)), jnp.float32)
    p = params["layer_3"]["moe"]

    def same(x):
        return x

    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.experts(h, p, REF_SIZES, same)
        got, sizes = models.routed_experts.RoutedExperts(
            CFG.experts_spec()).apply({"params": p}, h)
    assert "shared_experts" not in p
    assert CFG.experts_spec().norm_eps == 1e-6
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert int(np.asarray(sizes).sum()) == 12 * 2


# -- what is refused, with its reason -------------------------------------------

def test_what_a_model_with_state_layers_does_not_do_is_refused(params):
    with pytest.raises(NotImplementedError, match="kv_quant"):
        _engine(params, kv_quant="int8")
    with pytest.raises(NotImplementedError, match="state"):
        _server(params, enable_disagg=True)
    with pytest.raises(NotImplementedError, match="state"):
        _server(params, enable_kv_offload=True)
    # the server sizes the ring for its own verify width: 2 + 7 rows
    assert _server(params, spec_tokens=6).engine.state_rows == 12
    # a chunk is never rolled back, so it may be as wide as it likes
    assert _server(params, prefill_chunk=64).prefill_chunk == 64
    e = _engine(params)
    with pytest.raises(ValueError, match="verify launch of 7"):
        e.verify(np.zeros((3, 7), np.int32), [0, 0, 0], [0, 0, 0],
                 np.zeros((3, e.blocks_per_seq), np.int32))
    with pytest.raises(NotImplementedError, match="state"):
        e.export_blocks([1])
