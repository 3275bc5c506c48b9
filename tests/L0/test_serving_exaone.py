"""The ``exaone_moe`` family under ``DecodeEngine`` and
``InferenceServer``: window and full attention layers mixed, grouped
key-value heads, a share of the routed experts.  The pool by kind of
layer (``serving.kv_cache``: the table's blocks for the layers that keep
every token, a ring a slot for the window layers) against ONE full pass
of the plain reference (``benchmarks/reference/exaone_moe.py``).  Tiny
sizes, float32 weights and pool, on the CPU; the page loop with a lower
bound, in Pallas interpret mode, against the gathered jnp form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.models import CacheRow
from apex_tpu.models.family import layer_windows
from apex_tpu.ops.decode_attention import (
    grouped_attention_reference,
    paged_attention,
)
from apex_tpu.serving import InferenceServer, KVCacheConfig
from apex_tpu.serving.engine import DecodeEngine
from apex_tpu.serving.kv_cache import (
    WINDOW_LEAF,
    CacheView,
    init_kv_cache,
    ring_rows,
    slot_index,
)
from benchmarks.harness import weights
from benchmarks.reference import exaone_moe as ref

pytestmark = pytest.mark.serving

WINDOW, BS, CHUNK = 8, 4, 16
RING = 32                       # four windows, in whole blocks
SIZES = dict(
    vocab_size=211, hidden_size=64, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts=8,
    num_shared_experts=1, num_experts_per_tok=2, first_k_dense_replace=1,
    sliding_window=WINDOW, rms_norm_eps=1e-5, routed_scaling_factor=2.5,
    norm_topk_prob=True, max_position_embeddings=512)
CFG = models.ExaoneMoeConfig(**SIZES, initializer_range=0.3)
REF_SIZES = dict(SIZES, layer_types=list(CFG.kinds),
                 rope_parameters={"rope_theta": 1e6},
                 assumed={"initializer_range": 0.3})


@pytest.fixture(scope="module")
def params():
    p = weights.make_params(ref.param_table(REF_SIZES), 31, jnp.float32, 0.3)
    p["block_5"]["moe"]["e_score_correction_bias"] = jnp.asarray(
        np.random.default_rng(31).normal(size=8) * 0.3, jnp.float32)
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


# -- what the family tells the engine ------------------------------------------

def test_the_family_says_what_each_layer_keeps():
    assert CFG.kinds == ("sliding_attention",) * 3 + ("full_attention",) \
        + ("sliding_attention",) * 3 + ("full_attention",)
    assert layer_windows(CFG) == (8, 8, 8, None, 8, 8, 8, None)
    row = CFG.cache_row()
    assert (row.kind, row.groups, row.group_width, row.heads_per_group,
            row.heads, row.shared) == ("kv", 2, 32, 2, 4, True)
    # the families that keep every token in every layer answer so, and
    # a head of theirs is read by itself alone
    gpt = models.GPTConfig(vocab_size=64, hidden_size=32,
                           num_hidden_layers=3, num_attention_heads=2,
                           intermediate_size=64, max_position_embeddings=32)
    assert layer_windows(gpt) == (None,) * 3
    assert not gpt.cache_row().shared
    assert CacheRow.kv(4, 16) == CacheRow("kv", 4, 32, 1, (16, 32), 32)
    with pytest.raises(ValueError, match="do not divide"):
        CacheRow.kv(6, 16, 4)
    with pytest.raises(ValueError, match="layer_types"):
        models.ExaoneMoeConfig(**dict(SIZES, num_hidden_layers=2),
                               layer_types=("full_attention",))


# -- (a) chunk prefill then decode through the pool ------------------------------

@pytest.mark.parametrize("n", [5, 8, 16, 17, 41, 70])
def test_chunked_prefill_then_decode_match_the_references_full_pass(
        params, reference_logits, n):
    """Prompts shorter than the window of 8, equal to it, on a chunk's
    edge, a token past it, five windows long and longer than two rings
    (70 of 32 rows), in chunks of 16 over blocks of 4.  Then six decode
    steps fed the reference's own tokens.  Logits at every step against
    ONE full pass of the plain reference over the whole row."""
    with jax.default_matmul_precision("highest"):
        e = _engine(params)
        prompt = _prompt(n, n)
        blocks = e.allocator.alloc(e.blocks_per_seq)
        last = None
        for start in range(0, n, CHUNK):
            last = e.chunk_prefill(prompt[start:start + CHUNK], start,
                                   blocks, pad_to=CHUNK, slot=1)
        row, got = list(prompt), [np.asarray(last)]
        tables = np.zeros((3, e.blocks_per_seq), np.int32)
        tables[1] = blocks
        for _ in range(6):
            row.append(int(np.argmax(got[-1])))
            logits = e.decode([0, row[-1], 0], [0, len(row) - 1, 0], tables)
            got.append(np.asarray(logits)[1])
    want = reference_logits(row)
    for i, g in enumerate(got):
        np.testing.assert_allclose(g, want[n - 1 + i], atol=3e-4, rtol=3e-4)
    assert e.attention_paths == dict.fromkeys(
        ("decode", "verify", "chunk_prefill"), "gathered")


# -- (b) verify with rejected drafts on a window layer ---------------------------

def test_rejected_drafts_leave_a_window_layer_as_it_was(params,
                                                       reference_logits):
    """Two slots at different depths, five fed rows each, every row's
    logits; then slot 0 keeps two of its five (three drafts rejected)
    and goes on from there: the rejected rows lie past the accepted
    length in the ring and are written over before anything reads them,
    and what they wrote over lay a ring behind."""
    with jax.default_matmul_precision("highest"):
        e = _engine(params)
        rows = [_prompt(41, 60), _prompt(42, 11)]
        tables = np.zeros((3, e.blocks_per_seq), np.int32)
        fed = [43, 6]                       # cached before the verify
        for i, r in enumerate(rows):
            blocks = e.allocator.alloc(e.blocks_per_seq)
            tables[i] = blocks
            for start in range(0, fed[i], CHUNK):
                e.chunk_prefill(r[start:min(fed[i], start + CHUNK)], start,
                                blocks, pad_to=CHUNK, slot=i)
        tokens = np.zeros((3, 5), np.int32)
        tokens[0] = rows[0][43:46] + [7, 9]          # two wrong drafts
        tokens[1, :3] = rows[1][6:9]
        logits = np.asarray(e.verify(tokens, [5, 3, 0], fed + [0], tables))
        # slot 0 accepted positions 43 and 44 and goes on with the real
        # row from 45; slot 1 accepted all three
        again = np.zeros((3, 5), np.int32)
        again[0] = rows[0][45:50]
        again[1, :2] = rows[1][9:11]
        after = np.asarray(e.verify(again, [5, 2, 0], [45, 9, 0], tables))
    want = [reference_logits(r) for r in rows]
    np.testing.assert_allclose(logits[0, :3], want[0][43:46], atol=3e-4,
                               rtol=3e-4)
    np.testing.assert_allclose(logits[1, :3], want[1][6:9], atol=3e-4,
                               rtol=3e-4)
    np.testing.assert_allclose(after[0], want[0][45:50], atol=3e-4,
                               rtol=3e-4)
    np.testing.assert_allclose(after[1, :2], want[1][9:11], atol=3e-4,
                               rtol=3e-4)


# -- the server with its defaults; (c) a shared prefix; (d) preemption -----------

def test_the_server_serves_the_family_with_its_defaults(
        params, reference_logits):
    """Chunked prefill, the pipelined loop and speculation as they come;
    the tokens are the reference's greedy ones."""
    with jax.default_matmul_precision("highest"):
        srv = _server(params)
        prompts = [_prompt(51, 45), _prompt(52, 9), _prompt(53, 3)]
        outs = srv.generate(prompts, max_new_tokens=8)
    for p, o in zip(prompts, outs):
        assert o == _greedy_by_reference(reference_logits, p, 8)
    st = srv.stats()
    assert st["memory"]["cache_kind"] == "kv"
    # 2 key-value heads of K | V, 16 each, in float32
    assert st["memory"]["row_bytes_per_token_layer"] == 2 * 32 * 4
    assert set(k.split("[")[0] for k in st["programs"]["by_program"]) \
        >= {"chunk_prefill_sampled", "decode_sampled"}


def test_a_prefix_longer_than_the_window_is_served_as_the_reference_has_it(
        params, reference_logits):
    """Two requests share 40 tokens, five windows: a hit would need the
    window layers' rows 32 .. 39, which the first request's ring has
    let go or handed to another, so no hit is taken for a model with
    window layers; the family's answer decides, not an argument.  Both
    get the reference's tokens."""
    shared = _prompt(61, 40)
    with jax.default_matmul_precision("highest"):
        srv = _server(params)
        first = srv.generate([shared + _prompt(62, 3)], max_new_tokens=5)[0]
        second = srv.generate([shared + _prompt(63, 6)],
                              max_new_tokens=5)[0]
        again = srv.generate([shared + _prompt(63, 6)], max_new_tokens=5)[0]
    assert first == _greedy_by_reference(
        reference_logits, shared + _prompt(62, 3), 5)
    assert second == again == _greedy_by_reference(
        reference_logits, shared + _prompt(63, 6), 5)
    assert srv.prefix_cache is None
    assert srv.prefix.count("prefix_hit_tokens") == 0
    assert "prefix_hit_tokens" not in srv.stats()


def test_preemption_and_re_prefill_leave_the_tokens_as_they_were(params):
    """A pool too small for both requests' whole rows: one is preempted
    and prefilled again, into whichever slot's ring it is given then;
    the tokens are a roomy server's."""
    prompts = [_prompt(71, 50), _prompt(72, 44)]
    with jax.default_matmul_precision("highest"):
        tight = _server(params, num_blocks=29)
        got = tight.generate(prompts, max_new_tokens=14)
        roomy = _server(params)
        want = roomy.generate(prompts, max_new_tokens=14)
    assert got == want
    assert tight.stats()["preemptions"] >= 1
    assert roomy.stats()["preemptions"] == 0


# -- (e) the share of the experts -----------------------------------------------

def test_the_shares_of_all_sixteen_ranges_add_up_to_the_uncut_layer():
    """The ``model-configs`` guide's share test.  An expert layer of 16
    routed experts, 8 a token, cut into 16 ranges of one
    (``experts_held``): each range routes over all 16 and computes its
    own expert's part beside the shared expert.  The parts, the shared
    expert counted once, add up to what the uncut reference gives for
    the whole layer; and a range of the program's layer is the same
    range of the reference's."""
    sizes = dict(REF_SIZES, num_experts=16, num_experts_per_tok=8,
                 num_hidden_layers=2)
    sizes["layer_types"] = REF_SIZES["layer_types"][:2]
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(1, 12, 64)), jnp.float32)
    moe = {k.split("/", 2)[2]: v for k, v in ref.param_table(sizes).items()
           if k.startswith("block_1/moe/")}
    p = weights.make_params(moe, 9, jnp.float32, 0.3)
    p["e_score_correction_bias"] = jnp.asarray(
        rng.normal(size=16) * 0.2, jnp.float32)

    def same(x):
        return x

    with jax.default_matmul_precision("highest"):
        whole, _, _ = ref.experts(h, p, sizes, same)
        shared = ref.gated(h, p["shared_experts"], same)
        parts = []
        for first in range(16):
            cut = dict(sizes, num_experts=1, experts_held_first=first,
                       published={"num_experts": 16})
            mine = dict(p, **{k: p[k][first:first + 1] for k in (
                "experts_gate_proj", "experts_up_proj",
                "experts_down_proj")})
            part, _, _ = ref.experts(h, mine, cut, same)
            parts.append(part - shared)
            cfg = models.ExaoneMoeConfig(**dict(
                SIZES, num_experts=16, num_experts_per_tok=8,
                num_hidden_layers=2), experts_held=(first, 1))
            got, given = models.routed_experts.RoutedExperts(
                cfg.experts_spec()).apply({"params": mine}, h)
            np.testing.assert_allclose(np.asarray(got), np.asarray(part),
                                       atol=2e-5, rtol=2e-5)
            assert given.shape == (1,)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=5e-5, rtol=5e-5)


# -- (f) the page loop with a lower bound ----------------------------------------

def _ring_pool(rng, starts, fed, groups, d, window, ring_blocks, bs):
    """A pool leaf of two layers whose layer 1 holds each sequence's
    rows, position ``p`` at ring row ``p % ring`` of its own ring (or at
    row ``p`` of its own blocks where ``window`` is None), the keys and
    values they are, and the tables."""
    b = len(starts)
    nb = ring_blocks if window is not None else \
        max(-(-(s + fed) // bs) for s in starts) + 1
    pool = np.zeros((2, (b * nb + 1) * bs, groups * 2 * d), np.float32)
    tables = 1 + np.arange(b)[:, None] * nb + np.arange(nb)[None]
    t = max(s + fed for s in starts)
    k = np.zeros((b, t, groups, d), np.float32)
    v = np.zeros_like(k)
    k_pos = -np.ones((b, t), np.int32)
    for i, s in enumerate(starts):
        n = s + fed
        k[i, :n] = rng.normal(size=(n, groups, d))
        v[i, :n] = rng.normal(size=(n, groups, d))
        k_pos[i, :n] = np.arange(n)
        for p in range(n):
            e = p % (nb * bs) if window is not None else p
            pool[1, tables[i, e // bs] * bs + e % bs] = np.concatenate(
                [k[i, p], v[i, p]], -1).reshape(-1)
    return pool, tables, k, v, k_pos


@pytest.mark.parametrize("fed,window,d", [
    (fed, window, 64) for fed in (1, 5, 24, 70) for window in (None, 8, 24)
] + [(fed, window, 128) for fed in (1, 70) for window in (None, 24)])
def test_the_page_loop_with_a_lower_bound_matches_the_gathered_form(
        fed, window, d):
    """``paged_attention`` in interpret mode, decode (one row), verify
    (five) and chunk (24; 70 of 8 heads a group are two row tiles),
    groups of 4 and 8 query heads against one ``K | V`` group, on a
    layer that keeps every token and on window layers whose ring has
    wrapped many times (position 1,000 of a ring of 64), against
    ``grouped_attention_reference`` over the whole sequences.  Heads of
    64 meet the whole ``K | V`` group; a key of 128, whole lane tiles,
    is sliced from it (the queries are as wide as the key alone)."""
    hpg = 8 if fed == 70 else 4
    ring_blocks = 2 if window == 8 and fed < 24 else 8
    starts = [0, 3, 37, 130, 1000 if window else 200]
    rng = np.random.default_rng(fed * 100 + (window or 0))
    pool, tables, k, v, k_pos = _ring_pool(rng, starts, fed, 2, d, window,
                                           ring_blocks, 16)
    q = jnp.asarray(rng.normal(size=(len(starts), fed, 2 * hpg, d)),
                    jnp.float32)
    got = paged_attention(
        q, jnp.asarray(pool), 1, jnp.asarray(tables, jnp.int32),
        jnp.asarray(starts, jnp.int32), block_size=16,
        heads_per_group=hpg, window=window, interpret=True)
    q_pos = np.asarray(starts)[:, None] + np.arange(fed)[None]
    want = grouped_attention_reference(
        q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(q_pos),
        jnp.asarray(k_pos), window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_a_ring_too_small_for_the_fed_rows_is_refused():
    q = jnp.zeros((1, 20, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="cannot hold a window"):
        paged_attention(q, jnp.zeros((1, 48, 256), jnp.float32), 0,
                        jnp.asarray([[1, 2]], jnp.int32),
                        jnp.zeros((1,), jnp.int32), block_size=16,
                        window=16, interpret=True)


@pytest.mark.parametrize("rows,start", [(1, 0), (1, 45), (5, 7), (5, 30),
                                        (24, 3), (24, 61)])
def test_the_table_path_interpreted_matches_the_gathered_path(rows, start):
    """``CacheView.attend`` on a row of two key-value heads that two
    query heads each read, a full layer and a window layer of a model
    that has both: Pallas in interpret mode through the table or the
    ring against the gathered jnp form; both write the same rows, the
    window layer's into slot 2's ring."""
    rng = np.random.default_rng(rows * 100 + start)
    row = CacheRow.kv(4, 64, 2)
    bs, window = 8, 8
    ring = ring_rows(window, bs)
    cfg = KVCacheConfig(num_layers=2, num_heads=2, head_dim=64,
                        num_blocks=14, block_size=bs, dtype=jnp.float32)
    cache = init_kv_cache(cfg)
    cache["kv"] = jnp.asarray(rng.normal(size=cache["kv"].shape),
                              jnp.float32)
    cache[WINDOW_LEAF] = jnp.asarray(rng.normal(
        size=(3, (3 * ring // bs + 1) * bs, 256)), jnp.float32)
    layers = ((WINDOW_LEAF, 0, window), ("kv", 0, None),
              (WINDOW_LEAF, 1, window), (WINDOW_LEAF, 2, window),
              ("kv", 1, None))
    tables = jnp.asarray([[3, 1, 4, 2, 7, 5, 9, 6, 8, 10, 11, 12]],
                         jnp.int32)
    starts = jnp.asarray([start], jnp.int32)
    pos = start + jnp.arange(rows, dtype=jnp.int32)[None]
    slots = slot_index(tables, pos, bs)
    q = jnp.asarray(rng.normal(size=(1, rows, 4, 64)), jnp.float32)
    fresh = tuple(jnp.asarray(rng.normal(size=(1, rows, 2, 64)),
                              jnp.float32) for _ in range(2))
    for layer, leaf in ((2, WINDOW_LEAF), (4, "kv")):
        outs = []
        for table in (False, True):
            view = CacheView(cache, tables, starts, slots, block_size=bs,
                             row=row, table=table,
                             ring=jnp.asarray([2], jnp.int32),
                             layers=layers)
            ctx, after = view.attend(layer, q, fresh)
            assert ctx.shape == (1, rows, 4, 64)
            outs.append((np.asarray(ctx), after.cache))
        np.testing.assert_allclose(outs[0][0], outs[1][0], atol=2e-5,
                                   rtol=2e-5)
        for name in cache:
            np.testing.assert_array_equal(np.asarray(outs[0][1][name]),
                                          np.asarray(outs[1][1][name]))
            changed = np.any(np.asarray(outs[0][1][name])
                             != np.asarray(cache[name]))
            assert changed == (name == leaf)
        if leaf == WINDOW_LEAF:
            # ... and of the rings, slot 2's alone
            moved = np.any(np.asarray(outs[0][1][leaf])
                           != np.asarray(cache[leaf]), axis=(0, 2))
            assert not np.any(moved[:bs + 2 * ring])


# -- (g) a window layer's pool never holds more than its ring ----------------------

def test_a_window_layers_pool_is_a_ring_a_slot_at_any_length(params):
    """The window layers' leaf is 3 slots of 32 rows and the garbage
    block, whatever ``max_context`` is; a request four rings long
    touches its own ring's rows and no other's, and ``stats()`` counts
    the rows the rings hold and have let go."""
    with jax.default_matmul_precision("highest"):
        srv = _server(params, max_context=256, enable_speculation=False)
        e = srv.engine
        assert e.ring_rows == RING == ring_rows(WINDOW, BS)
        assert e.max_fed_rows == RING - WINDOW
        leaf = e.cache[WINDOW_LEAF]
        assert leaf.shape == (6, 3 * RING + BS, 2 * 32)
        assert e.cache["kv"].shape == (2, (3 * 64 + 1) * BS, 2 * 32)
        req = srv.submit(_prompt(91, 120), 10)
        while srv.has_work:
            srv.step()
            mem = srv.stats()["memory"]["by_kind"]
            if req.running:
                assert mem["window"]["rows_live"] == min(req.num_cached,
                                                         RING)
                assert mem["window"]["rows_let_go_live"] == max(
                    0, req.num_cached - RING)
    leaf = np.asarray(e.cache[WINDOW_LEAF])
    # one slot was used: its ring is full, the others' rows are as made
    rings = leaf[:, BS:].reshape(6, 3, RING, -1)
    used = [bool(np.any(rings[:, s])) for s in range(3)]
    assert sum(used) == 1
    assert np.all(np.any(rings[:, used.index(True)] != 0, axis=-1))
    mem = srv.stats()["memory"]
    assert mem["by_kind"]["window"]["rows_a_slot"] == RING
    assert mem["by_kind"]["window"]["rows_usable"] == 3 * RING
    assert mem["by_kind"]["window"]["rows_let_go"] == 129 - RING
    assert mem["by_kind"]["full"]["blocks_usable"] == 3 * 64
    assert mem["pool_bytes"] == (6 * (3 * RING + BS)
                                 + 2 * (3 * 64 + 1) * BS) * 64 * 4
    assert mem["pool_bytes_per_device"] == mem["pool_bytes"]
    ex = srv.stats()["experts"]
    assert ex["enabled"] and ex["layers"] == 7 and ex["experts_held"] == 8
    assert ex["rows_routed"] == (120 + 9) * 7 * 2


# -- what is refused, with its reason -------------------------------------------

def test_what_a_model_with_window_layers_does_not_do_yet_is_refused(params):
    with pytest.raises(NotImplementedError, match="kv_quant"):
        _engine(params, kv_quant="int8")
    with pytest.raises(NotImplementedError, match="ring"):
        _server(params, enable_disagg=True)
    with pytest.raises(NotImplementedError, match="ring"):
        _server(params, enable_kv_offload=True)
    with pytest.raises(ValueError, match="ring holds"):
        _server(params, prefill_chunk=32)
    # the default chunk is what the ring takes
    assert _server(params, prefill_chunk=None).prefill_chunk == 24
    e = _engine(params)
    with pytest.raises(ValueError, match="fed rows"):
        e.chunk_prefill(_prompt(1, 30), 0, e.allocator.alloc(8), pad_to=32)
    with pytest.raises(NotImplementedError, match="ring"):
        e.export_blocks([1])
