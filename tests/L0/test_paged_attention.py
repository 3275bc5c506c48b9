"""Attention in place (``docs/serving.md``, "Attend through the
table"): ``ops.paged_attention`` reads the pool through the block table
(the kernel in Pallas interpret mode on the CPU) and must agree with
the gathered form it replaces — ``gather_context`` into the jnp oracle
of ``ops.cached_attention`` (one query row) or into
``ops.chunk_cached_attention`` (several), and for grouped heads, a
window over a ring and a latent row into ``grouped_attention_reference``
or ``latent_attention_reference`` — for every length a slot can have,
whatever its table looks like and however long; its grid runs over
slots and row tiles, never over the table; and a server built on the
table path must serve the tokens the gathered path serves."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.models import CacheRow
from apex_tpu.ops.decode_attention import (
    _reference,
    chunk_cached_attention,
    grouped_attention_reference,
    latent_attention_reference,
    paged_attention,
    paged_attention_fits,
)
from apex_tpu.serving import InferenceServer, KVCacheConfig
from apex_tpu.serving.kv_cache import (
    WINDOW_LEAF,
    BlockAllocator,
    CacheView,
    block_slots,
    context_bias,
    gather_context,
    init_kv_cache,
    read_slots,
    slot_index,
    write_prefill,
)

pytestmark = pytest.mark.serving

BS, NB, LAYERS = 16, 10, 2          # 160 positions: two 8-page windows
T = BS * NB
# a table far longer than any context in it, as a server's is
LONG = 64


class Form(collections.namedtuple(
        "Form", "name heads groups d window latent")):
    """What a layer keeps a token and how its rows are read: ``heads``
    query heads over ``groups`` key-value heads of ``d`` (a ``K | V``
    pair each), or one latent row of ``2 * d`` lanes that all heads read
    (``latent``: the value's width and the row's used width); ``window``
    a ring read with a lower bound."""

    @property
    def row(self):
        if self.latent:
            value, used = self.latent
            return CacheRow.latent(value, used - value, self.heads)
        return CacheRow.kv(self.heads, self.d, self.groups)

    @property
    def leaf(self):
        return WINDOW_LEAF if self.window else "kv"


def _mha(heads):
    return Form(str(heads), heads, heads, 64, None, None)


# 4 query heads a key-value head, a key of whole lane tiles sliced from
# its group; a window of 128 over a ring of 32 pages (two of the loop's
# 16-page windows); a latent row of 256 lanes, 144 used, 32 pages a
# window
GROUPED = Form("grouped", 8, 2, 128, None, None)
RING = Form("ring", 8, 2, 64, 128, None)
LATENT = Form("latent", 4, 1, 128, None, (128, 144))


def _rand(rng, *shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _scene(start, rows, form, dtype, seed=0, nb=NB):
    """A pool with four sequences in it and ``rows`` fresh rows each:

    0. ``start`` cached positions (the case under test), blocks in
       shuffled order, table entries beyond them 0 (the garbage block);
    1. an idle slot: position 0, an all-zero table (a prompt's first
       chunk, blocks of its own, where the rows overrun a page);
    2. the full context less the fresh rows, blocks shuffled;
    3. shares sequence 2's first three physical blocks (a prefix-cache
       hit) and goes on in blocks of its own.

    A window layer's table is each slot's ring of ``nb`` blocks: the
    newest ``nb * BS`` positions of a sequence, position ``p`` at ring
    row ``p % (nb * BS)``."""
    heads = form.heads
    rng = np.random.default_rng([seed, start, rows, heads])
    cfg = KVCacheConfig(LAYERS, form.groups, form.d, num_blocks=1 + 4 * nb,
                        block_size=BS, dtype=dtype)
    alloc = BlockAllocator(cfg)
    order = rng.permutation(alloc.alloc(4 * nb)).tolist()
    t = nb * BS
    starts = np.array([start, 0, t - rows, 3 * BS + 5], np.int32)
    tables = np.zeros((4, nb), np.int32)
    if form.window:
        tables[:] = 1 + np.arange(4)[:, None] * nb + np.arange(nb)
        starts[2] = 7 * t + 11
    else:
        for b in (0, 2) + ((1,) if rows > BS else ()):
            n = -(-(starts[b] + rows) // BS)
            tables[b, :n], order = order[:n], order[n:]
        tables[3, :3] = tables[2, :3]
        for e in range(3, -(-(starts[3] + rows) // BS)):
            tables[3, e] = order.pop()
    cache = {form.leaf: init_kv_cache(cfg)["kv"]}
    for b in (0, 2, 3):             # 3 rewrites the shared blocks alike
        n = int(starts[b])
        if b == 3 and not form.window:  # the shared prefix is 2's
            lo = 3 * BS
        else:                       # a ring keeps the newest rows
            lo = max(0, n - t) if form.window else 0
        if n > lo:
            kv = tuple(_rand(rng, LAYERS, 1, n - lo, form.groups, form.d,
                             dtype=dtype) for _ in range(2))
            pos = jnp.arange(lo, n, dtype=jnp.int32)[None] % t
            cache[form.leaf] = write_prefill(
                {"kv": cache[form.leaf]}, kv,
                slot_index(jnp.asarray(tables[b:b + 1]), pos, BS))["kv"]
    if form.latent:                 # the lanes past the row's are 0
        cache["kv"] = cache["kv"].at[..., form.latent[1]:].set(0)
        q = _rand(rng, 4, rows, heads, form.latent[1], dtype=dtype)
        fresh = _rand(rng, 4, rows, form.latent[1], dtype=dtype)
    else:
        q = _rand(rng, 4, rows, heads, form.d, dtype=dtype)
        fresh = tuple(_rand(rng, 4, rows, form.groups, form.d, dtype=dtype)
                      for _ in range(2))
    pos = jnp.asarray(starts)[:, None] + jnp.arange(rows)[None]
    slots = slot_index(jnp.asarray(tables), pos % t, BS)
    return cfg, cache, jnp.asarray(tables), jnp.asarray(starts), slots, \
        q, fresh


def _oracle(cfg, cache, tables, starts, q, fresh, layer):
    """The gathered form: every sequence's whole context out of the
    pool, the fresh rows behind it, the ops as they always were."""
    t = tables.shape[1] * BS
    k_ctx, v_ctx = gather_context(cache, tables, BS, cfg.num_heads)
    k = jnp.concatenate([k_ctx[layer], fresh[0]], axis=1)
    v = jnp.concatenate([v_ctx[layer], fresh[1]], axis=1)
    bias = context_bias(starts, t)
    if q.shape[1] == 1:
        bias = jnp.concatenate([bias, jnp.zeros((q.shape[0], 1))], axis=1)
        return _reference(q, k, v, bias, 1.0 / np.sqrt(cfg.head_dim))
    return chunk_cached_attention(q, k, v, bias)


def _oracle_by_position(form, cache, tables, starts, q, layer, scale):
    """The gathered form of grouped, window and latent layers: each
    sequence's rows gathered through its table (or ring) from the pool
    the fresh rows were written to, at the positions they stand for,
    into ``grouped_attention_reference`` or
    ``latent_attention_reference``."""
    b, nb = tables.shape
    t, rows = nb * BS, q.shape[1]
    q_pos = starts[:, None] + jnp.arange(rows, dtype=jnp.int32)[None]
    k_ctx, v_ctx = gather_context({"kv": cache[form.leaf]}, tables, BS,
                                  form.groups)
    if form.latent:
        kv = jnp.concatenate([k_ctx[layer], v_ctx[layer]], axis=-1)
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, 2 * form.d - q.shape[-1]),))
        return latent_attention_reference(
            q, kv.reshape(b, t, -1), q_pos, value=form.latent[0],
            scale=scale)
    k_pos = jnp.arange(t, dtype=jnp.int32)[None]
    if form.window:     # ring row i: the newest position that is i mod t
        newest = q_pos[:, -1:]
        k_pos = newest - (newest - k_pos) % t
    return grouped_attention_reference(q, k_ctx[layer], v_ctx[layer],
                                       q_pos, jnp.broadcast_to(k_pos, (b, t)),
                                       window=form.window)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


def _case(start, rows, form, dtype, nb=NB):
    name = f"{start}-{rows}-{form.name}-{jnp.dtype(dtype).name}"
    return pytest.param(start, rows, form, dtype, nb,
                        id=name if nb == NB else f"table{nb}-{name}")


_KINDS = ((_mha(25), jnp.bfloat16), (_mha(16), jnp.float32))
_CASES = (
    # every length a slot can have in a table of two windows
    [_case(start, rows, form, dtype)
     for start in (0, 1, BS - 1, BS, BS + 1, T - 5) for rows in (1, 5)
     for form, dtype in _KINDS]
    # contexts of 0, 1, 16, 17 and 63 pages in a table far longer than
    # any of them; a latent row's window is 32 pages
    + [_case(pages * BS, rows, form, dtype, LONG)
       for form, dtype in (_KINDS[0], (GROUPED, jnp.float32),
                           (LATENT, jnp.float32))
       for pages in (0, 1, 16, 17, 63) for rows in (1, 5)]
    # verify rows across the edge of the loop's window (128, 256 and 512
    # keys), and a chunk whose two row tiles end in different windows
    + [_case(start, rows, form, dtype, LONG)
       for form, dtype, start, chunk, chunk_start in (
           (*_KINDS[0], 254, 200, 100), (GROUPED, jnp.float32, 510, 200, 100),
           (LATENT, jnp.float32, 509, 300, 250))
       for start, rows in ((start, 5), (chunk_start, chunk))]
    # a window of 128 keys whose reach crosses the loop's windows, in a
    # ring that has wrapped
    + [_case(start, rows, RING, jnp.float32, 32)
       for start, rows in ((0, 1), (800, 1), (800, 5), (3, 5), (900, 200))])


@pytest.mark.parametrize("start,rows,form,dtype,nb", _CASES)
def test_table_path_matches_gathered_oracle(start, rows, form, dtype, nb):
    cfg, cache, tables, starts, slots, q, fresh = _scene(
        start, rows, form, dtype, nb=nb)
    layers = ((WINDOW_LEAF, 0, form.window),
              (WINDOW_LEAF, 1, form.window)) if form.window else None
    view = CacheView(cache, tables, starts, slots, block_size=BS,
                     row=form.row, table=True, layers=layers)
    layer, scale = 1, 0.17 if form.latent else None
    got, after = view.attend(layer, q, fresh, scale=scale)
    if form.groups == form.heads and not form.latent:
        want = _oracle(cfg, cache, tables, starts, q, fresh, layer)
    else:
        want = _oracle_by_position(form, after.cache, tables, starts, q,
                                   layer, scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))
    if form.window:
        return
    # the fresh rows are where the table says, in the written layer only
    groups = form.groups if not form.latent else 1
    rows_at = read_slots({"kv": after.cache["kv"]}, slots.reshape(-1),
                         groups)
    if form.latent:
        written = jnp.concatenate([rows_at["k"], rows_at["v"]], -1)
        np.testing.assert_array_equal(
            np.asarray(written[layer][..., :form.latent[1]], np.float32),
            np.asarray(fresh.reshape(-1, 1, form.latent[1]), np.float32))
    else:
        np.testing.assert_array_equal(
            np.asarray(rows_at["k"][layer], np.float32),
            np.asarray(fresh[0].reshape(-1, groups, form.d), np.float32))
        np.testing.assert_array_equal(
            np.asarray(rows_at["v"][layer], np.float32),
            np.asarray(fresh[1].reshape(-1, groups, form.d), np.float32))
    other = read_slots({"kv": cache["kv"]}, slots.reshape(-1), groups)["k"][0]
    np.testing.assert_array_equal(np.asarray(rows_at["k"][0], np.float32),
                                  np.asarray(other, np.float32))


@pytest.mark.parametrize("rows", [1, 5])
def test_gathered_view_is_the_oracle(rows):
    cfg, cache, tables, starts, slots, q, fresh = _scene(
        BS + 1, rows, _mha(4), jnp.float32)
    view = CacheView(cache, tables, starts, slots, block_size=BS,
                     row=CacheRow.kv(4, 64), table=False)
    got, after = view.attend(0, q, fresh)
    want = _oracle(cfg, cache, tables, starts, q, fresh, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)
    table_view = CacheView(cache, tables, starts, slots, block_size=BS,
                           row=CacheRow.kv(4, 64), table=True)
    np.testing.assert_array_equal(
        np.asarray(after.cache["kv"]),
        np.asarray(table_view.attend(0, q, fresh)[1].cache["kv"]))


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("form", [_mha(4), GROUPED, RING, LATENT],
                         ids=lambda f: f.name)
@pytest.mark.parametrize("rows", [1, 5, 200])
def test_the_grid_runs_over_slots_and_row_tiles_not_the_table(form, rows):
    """The page loop's grid is (slots, row tiles) whatever the table's
    length: the windows a slot holds are walked inside the kernel, so a
    table ten times longer adds no grid step."""
    b = 3
    d = form.d if not form.latent else form.latent[1]
    q = jnp.zeros((b, rows, form.heads, d), jnp.float32)
    kw = dict(block_size=BS, heads_per_group=form.heads // form.groups,
              window=form.window, interpret=True)
    if form.latent:
        kw = dict(block_size=BS, scale=0.1, latent_value=form.latent[0],
                  interpret=True)
        q = jnp.zeros((b, rows, form.heads, 2 * form.d), jnp.float32)
    pool = jnp.zeros((2, 16 * BS, form.groups * 2 * form.d), jnp.float32)
    grids = []
    for nb in (32, 320):
        jaxpr = jax.make_jaxpr(lambda q, p, t, s: paged_attention(
            q, p, 1, t, s, **kw))(q, pool, jnp.zeros((b, nb), jnp.int32),
                                  jnp.zeros((b,), jnp.int32))
        (call,) = _pallas_calls(jaxpr.jaxpr)
        grids.append(tuple(call.params["grid_mapping"].grid))
    shared = 1 if form.groups == form.heads and not form.latent \
        else form.heads // form.groups
    tile = 1024 if form.latent else 512 if shared > 1 or form.window \
        else 128
    assert grids[0] == grids[1] == (b, -(-rows * shared // tile))


@pytest.mark.parametrize("form,starts,rows,table,want", [
    # one decode row a slot: windows of 8 pages (128 keys) in a table of
    # 64 entries, 8 a slot
    (_mha(25), [0, 127, 128, 1000], 1, LONG, (1 + 1 + 2 + 8, 4 * 8)),
    # a chunk of 200 rows of 4 heads: two row tiles of 512 (128 and 72
    # positions), windows of 16 pages (256 keys); the tiles' last rows
    # are positions 227 and 299
    (GROUPED, [100], 200, LONG, (1 + 2, 2 * 4)),
    # a latent row: 32 pages a window, 1,040 entries, 33 a slot
    (LATENT, [1000, 0], 1, 1040, (2 + 1, 2 * 33)),
    # a table shorter than a window is one window
    (_mha(25), [5, 90], 5, 6, (2, 2)),
], ids=["decode", "chunk", "latent", "short-table"])
def test_page_windows_counts_what_the_loop_visits(form, starts, rows, table,
                                                  want):
    from apex_tpu.ops.decode_attention import page_windows
    assert page_windows(
        np.asarray(starts), rows, table, block_size=BS, heads=form.heads,
        heads_per_group=form.heads // form.groups,
        latent=bool(form.latent)) == want


def test_kernel_refuses_a_pool_it_cannot_tile():
    assert paged_attention_fits(64, 16, jnp.bfloat16)
    assert paged_attention_fits(64, 8, jnp.float32)
    assert not paged_attention_fits(64, 8, jnp.bfloat16)   # half a tile
    assert not paged_attention_fits(32, 16, jnp.bfloat16)  # 64 lanes
    q = jnp.zeros((1, 1, 2, 32), jnp.float32)
    pool = jnp.zeros((1, 32, 2 * 2 * 32), jnp.float32)
    with pytest.raises(ValueError, match="cannot tile"):
        paged_attention(q, pool, 0, jnp.zeros((1, 2), jnp.int32),
                        jnp.zeros((1,), jnp.int32), block_size=16)
    with pytest.raises(ValueError, match="pages must be"):
        paged_attention(q, pool[:, :, :64], 0,
                        jnp.zeros((1, 2), jnp.int32),
                        jnp.zeros((1,), jnp.int32), block_size=16)


# -- a server on each path -------------------------------------------------

VOCAB = 89


@pytest.fixture(scope="module")
def tiny():
    cfg = models.GPTConfig(
        vocab_size=VOCAB, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=128,
        max_position_embeddings=160, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    params = models.GPTLMHeadModel(cfg).init(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def _serve(cfg, params, tracer=None):
    """Three waves through a server: chunked prefill of a prompt longer
    than two chunks, a periodic prompt whose n-gram drafts bring verify
    in, a block-aligned repeat (a copy-on-write), a two-token request
    (a plain decode launch), then a round trip of two cached blocks
    through export and import and one more request over them."""
    rng = np.random.default_rng(11)
    server = InferenceServer(cfg, params, max_batch_size=4,
                             max_context=160, block_size=16,
                             prefill_chunk=32, cache_dtype=jnp.float32,
                             tracer=tracer)
    long = rng.integers(0, VOCAB, 80).tolist()
    periodic = rng.integers(0, VOCAB, 4).tolist() * 8
    wave1 = [long, periodic, rng.integers(0, VOCAB, 9).tolist()]
    out = server.generate(wave1, max_new_tokens=12)
    out += server.generate([list(long)], max_new_tokens=12)   # COW
    out += server.generate([wave1[2]], max_new_tokens=2)
    eng = server.engine
    held = eng.allocator.alloc(2)
    eng.import_blocks(held, eng.export_blocks([1, 2]))
    moved = read_slots(eng.cache, block_slots(held, 16), 2)
    kept = read_slots(eng.cache, block_slots([1, 2], 16), 2)
    for name in kept:
        np.testing.assert_array_equal(np.asarray(moved[name]),
                                      np.asarray(kept[name]))
    eng.allocator.free(held)
    out += server.generate([periodic + long[:20]], max_new_tokens=8)
    return server, out


def test_table_server_serves_what_the_gathered_server_serves(
        tiny, monkeypatch):
    from apex_tpu.observability import SpanTracer
    cfg, params = tiny
    gathered, want = _serve(cfg, params, tracer=SpanTracer())
    assert set(gathered.stats()["programs"]["attention"].values()) == \
        {"gathered"}
    import apex_tpu.serving.engine as engine_mod
    monkeypatch.setattr(engine_mod, "pallas_auto_gate", lambda: True)
    table, got = _serve(cfg, params, tracer=SpanTracer())
    st = table.stats()
    # a traced launch through the table says which windows its page
    # loop walked: a table of 10 pages is two windows of 8 a slot, and a
    # launch of 4 slots' decode rows walks at least one a slot; the
    # gathered path walks none and says nothing
    for server, walked in ((gathered, False), (table, True)):
        spans = [sp for sp in server.tracer.spans()
                 if sp.name in ("launch", "chunk_prefill")]
        assert {sp.name for sp in spans} == {"launch", "chunk_prefill"}
        for sp in spans:
            args = sp.args or {}
            assert ("kv_windows" in args) == walked, sp
            if walked:
                slots = 1 if sp.name == "chunk_prefill" else 4
                assert args["kv_windows_table"] == 2 * slots
                assert slots <= args["kv_windows"] <= 2 * slots
    assert set(st["programs"]["attention"].values()) == {"table"}
    assert got == want
    # every mechanism ran on the table path
    assert st["prefix_cow_blocks"] >= 1
    assert st["speculation"]["verify_steps"] >= 1
    assert st["speculation"]["decode_steps"] >= 1
    families = {k.split("[")[0] for k in st["programs"]["by_program"]}
    assert {"chunk_prefill_sampled", "decode_sampled", "verify_sampled",
            "copy_blocks", "import_blocks"} <= families
    assert table.engine.memory_info()["decode_temp_bytes"] is not None


def test_int8_pool_and_mesh_keep_the_gathered_path(tiny, monkeypatch):
    """What the engine can see decides: the gate says kernels, and an
    int8 pool, a mesh or a geometry the kernel cannot tile still gather."""
    from jax.sharding import Mesh
    cfg, params = tiny
    import apex_tpu.serving.engine as engine_mod
    monkeypatch.setattr(engine_mod, "pallas_auto_gate", lambda: True)
    kw = dict(max_batch_size=2, max_context=64)
    for extra in (dict(kv_quant="int8"),
                  dict(mesh=Mesh(np.asarray(jax.devices()[:1]),
                                 ("model",))),
                  dict(block_size=8)):       # half a bf16 sublane tile
        eng = engine_mod.DecodeEngine(cfg, params, **kw, **extra)
        assert set(eng.attention_paths.values()) == {"gathered"}, extra
    eng = engine_mod.DecodeEngine(cfg, params, **kw)
    assert eng.attention_paths["decode"] == "table"


def test_sweep_times_the_page_loop_alone_and_gives_no_time_without_a_chip(
        capsys):
    """``tools/perf_sweep.py::sweep_paged`` launches ``paged_attention``
    alone at each shape, contexts at one page, the mix's mean and the
    whole table, those below it through the server's table and through
    one cut to the context; its time is the kernel's on the DEVICE's
    clock, so on the CPU a point carries an error and no number."""
    import json
    import os
    import sys
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "tools")
    sys.path.insert(0, tools)
    try:
        import perf_sweep
    finally:
        sys.path.remove(tools)
    assert set(perf_sweep.PAGED_SHAPES) == {"kexaone", "lfm2", "gpt2xl"}
    rows = perf_sweep.sweep_paged(
        shapes={"t": dict(slots=2, table=8, heads=4, kv_heads=2,
                          head_dim=64, mean=40)}, rows_list=(1, 20),
        iters=1)
    assert [(r["rows"], r["context"], r["table"]) for r in rows] == [
        (1, 16, 8), (1, 16, 1), (1, 40, 8), (1, 40, 3), (1, 128, 8),
        (20, 20, 8), (20, 20, 2), (20, 40, 8), (20, 40, 3), (20, 128, 8)]
    for r in rows:
        assert "ms" not in r and "error" in r
    printed = [json.loads(l) for l in capsys.readouterr().out.splitlines()
               if l.startswith("{")]
    assert printed == rows
