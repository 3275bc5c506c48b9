"""serving.kv_cache: the block pool's invariants.

The load-bearing properties: (1) block-table indirection is exact —
what a request writes through its table is what it gathers back,
regardless of which physical blocks it drew; (2) freed blocks are
REUSABLE without cross-talk — a new request overwriting a dead
request's blocks sees only its own data; (3) the dtype policy follows
amp.  Allocator bookkeeping (free-list, double-free, exhaustion) is
what the scheduler's correctness rests on.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving.kv_cache import (
    BlockAllocator,
    KVCacheConfig,
    context_bias,
    gather_context,
    init_kv_cache,
    pool_dtype,
    resolve_cache_dtype,
    slot_index,
    write_prefill,
    write_tokens,
)

pytestmark = pytest.mark.serving

NEG_INF = -1e9


def _cfg(**kw):
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 2)
    kw.setdefault("head_dim", 4)
    kw.setdefault("num_blocks", 8)
    kw.setdefault("block_size", 4)
    kw.setdefault("dtype", jnp.float32)
    return KVCacheConfig(**kw)


# -- allocator ------------------------------------------------------------

def test_allocator_never_hands_out_garbage_block():
    alloc = BlockAllocator(_cfg())
    got = alloc.alloc(7)
    assert sorted(got) == [1, 2, 3, 4, 5, 6, 7]   # block 0 reserved
    assert alloc.num_free == 0


def test_allocator_alloc_free_roundtrip_and_lifo_reuse():
    alloc = BlockAllocator(_cfg())
    a = alloc.alloc(3)
    b = alloc.alloc(2)
    assert len(set(a) | set(b)) == 5              # disjoint
    alloc.free(b)
    assert alloc.num_free == 4
    c = alloc.alloc(2)
    assert set(c) == set(b)                       # LIFO: freed come back

def test_allocator_exhaustion_raises_and_can_alloc_guards():
    alloc = BlockAllocator(_cfg())
    assert alloc.can_alloc(7) and not alloc.can_alloc(8)
    alloc.alloc(6)
    with pytest.raises(MemoryError):
        alloc.alloc(2)
    assert alloc.num_free == 1                    # failed alloc took nothing


def test_allocator_double_free_and_bad_ids_rejected():
    alloc = BlockAllocator(_cfg())
    blks = alloc.alloc(2)
    alloc.free(blks)
    with pytest.raises(ValueError):
        alloc.free([blks[0]])
    with pytest.raises(ValueError):
        alloc.free([0])                           # the garbage block
    with pytest.raises(ValueError):
        alloc.free([99])


def test_blocks_for():
    assert BlockAllocator.blocks_for(1, 4) == 1
    assert BlockAllocator.blocks_for(4, 4) == 1
    assert BlockAllocator.blocks_for(5, 4) == 2
    assert BlockAllocator.blocks_for(0, 4) == 1   # even empty needs a slot


def test_config_validation_and_sizing():
    with pytest.raises(ValueError):
        _cfg(num_blocks=1)                        # no room beside garbage
    cfg = _cfg()
    assert cfg.num_slots == 32
    assert cfg.usable_tokens == 28                # block 0 excluded
    assert cfg.bytes() == 2 * 2 * 32 * 2 * 4 * 4  # k+v,L,slots,H,D,fp32


# -- dtype policy ---------------------------------------------------------

def test_cache_dtype_defaults_to_bf16_and_explicit_wins():
    assert resolve_cache_dtype(None) == jnp.bfloat16
    assert resolve_cache_dtype(jnp.float32) == jnp.float32
    assert pool_dtype(init_kv_cache(_cfg(dtype=None))) == jnp.bfloat16


def test_cache_dtype_follows_amp_policy():
    """amp O2 (cast_model_type=fp16 override) => fp16 cache; the
    autouse _isolate_amp_state fixture clears the policy afterwards."""
    from apex_tpu import amp
    from apex_tpu.models import mlp

    amp.initialize(mlp.MLP([4]), opt_level="O2",
                   cast_model_type=jnp.float16, verbosity=0)
    assert resolve_cache_dtype(None) == jnp.float16


# -- device-side pure functions ------------------------------------------

def test_slot_index_scalar_and_sequence_forms():
    tables = jnp.array([[3, 1, 5], [2, 0, 0]], jnp.int32)
    # (B,) one position per sequence
    s = slot_index(tables, jnp.array([0, 5], jnp.int32), 4)
    np.testing.assert_array_equal(np.asarray(s), [3 * 4 + 0, 0 * 4 + 1])
    # (B, S) many positions per sequence
    s2 = slot_index(tables, jnp.array([[0, 4], [1, 2]], jnp.int32), 4)
    np.testing.assert_array_equal(np.asarray(s2),
                                  [[12, 1 * 4 + 0], [2 * 4 + 1, 2 * 4 + 2]])


def _fill(cfg, seed, b, s):
    rng = np.random.RandomState(seed)
    shape = (cfg.num_layers, b, s, cfg.num_heads, cfg.head_dim)
    return (jnp.asarray(rng.randn(*shape), jnp.float32),
            jnp.asarray(rng.randn(*shape), jnp.float32))


def test_write_prefill_then_gather_roundtrip():
    """What goes in through the table comes back in logical order."""
    cfg = _cfg()
    cache = init_kv_cache(cfg)
    alloc = BlockAllocator(cfg)
    table = alloc.alloc(2)                        # 8 token capacity
    n = 6                                         # partial last block
    k, v = _fill(cfg, 0, 1, n)
    tables = jnp.asarray([table + [0]], jnp.int32)
    slots = slot_index(tables, jnp.arange(n, dtype=jnp.int32)[None, :],
                       cfg.block_size)
    cache = write_prefill(cache, (k, v), slots)
    k_ctx, v_ctx = gather_context(cache, tables, cfg.block_size,
                                  cfg.num_heads)
    np.testing.assert_allclose(np.asarray(k_ctx[:, :, :n]),
                               np.asarray(k))
    np.testing.assert_allclose(np.asarray(v_ctx[:, :, :n]),
                               np.asarray(v))


def test_block_reuse_no_cross_talk():
    """Free request A's blocks, hand them to B: B's gather sees only
    B's writes (stale A data beyond B's length is masked by the ctx
    bias, which is part of the contract)."""
    cfg = _cfg()
    cache = init_kv_cache(cfg)
    alloc = BlockAllocator(cfg)
    table_a = alloc.alloc(2)
    ka, va = _fill(cfg, 1, 1, 8)
    tables_a = jnp.asarray([table_a], jnp.int32)
    slots = slot_index(tables_a,
                       jnp.arange(8, dtype=jnp.int32)[None, :],
                       cfg.block_size)
    cache = write_prefill(cache, (ka, va), slots)
    alloc.free(table_a)
    table_b = alloc.alloc(2)
    assert set(table_b) == set(table_a)           # physically reused
    kb, vb = _fill(cfg, 2, 1, 5)
    tables_b = jnp.asarray([table_b], jnp.int32)
    slots_b = slot_index(tables_b,
                         jnp.arange(5, dtype=jnp.int32)[None, :],
                         cfg.block_size)
    cache = write_prefill(cache, (kb, vb), slots_b)
    k_ctx, _ = gather_context(cache, tables_b, cfg.block_size,
                              cfg.num_heads)
    np.testing.assert_allclose(np.asarray(k_ctx[:, :, :5]),
                               np.asarray(kb))
    bias = context_bias(jnp.array([5]), 8)
    assert np.all(np.asarray(bias[0, :5]) == 0.0)
    assert np.all(np.asarray(bias[0, 5:]) <= NEG_INF)


def test_write_tokens_single_step_and_garbage_block_sink():
    cfg = _cfg()
    cache = init_kv_cache(cfg)
    alloc = BlockAllocator(cfg)
    t1, t2 = alloc.alloc(1), alloc.alloc(1)
    tables = jnp.asarray([t1, t2], jnp.int32)     # (2, 1)
    k, v = _fill(cfg, 3, 2, 1)                    # one token each
    slots = slot_index(tables, jnp.array([2, 0], jnp.int32),
                       cfg.block_size)
    cache = write_tokens(cache, (k, v), slots)
    k_ctx, _ = gather_context(cache, tables, cfg.block_size,
                                  cfg.num_heads)
    np.testing.assert_allclose(np.asarray(k_ctx[:, 0, 2]),
                               np.asarray(k[:, 0, 0]))
    np.testing.assert_allclose(np.asarray(k_ctx[:, 1, 0]),
                               np.asarray(k[:, 1, 0]))
    # an inactive slot (zeroed table) writes into physical block 0 —
    # which no allocated table can ever reference
    dead = jnp.zeros((1, 1), jnp.int32)
    kd, vd = _fill(cfg, 4, 1, 1)
    cache = write_tokens(cache, (kd, vd),
                         slot_index(dead, jnp.array([0], jnp.int32),
                                    cfg.block_size))
    k_ctx2, _ = gather_context(cache, tables, cfg.block_size,
                                  cfg.num_heads)
    np.testing.assert_allclose(np.asarray(k_ctx2[:, 0, 2]),
                               np.asarray(k[:, 0, 0]))  # untouched


def test_write_casts_to_cache_dtype_and_gather_casts_out():
    cfg = _cfg(dtype=jnp.bfloat16)
    cache = init_kv_cache(cfg)
    k, v = _fill(cfg, 5, 1, 1)                    # fp32 in
    cache = write_tokens(cache, (k, v),
                         jnp.array([4], jnp.int32))
    assert pool_dtype(cache) == jnp.bfloat16
    k_ctx, _ = gather_context(cache, jnp.asarray([[1]], jnp.int32),
                              cfg.block_size, cfg.num_heads,
                              out_dtype=jnp.float32)
    assert k_ctx.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(k_ctx[:, 0, 0]),
                               np.asarray(k[:, 0, 0]),
                               rtol=1e-2, atol=1e-2)  # bf16 roundtrip
