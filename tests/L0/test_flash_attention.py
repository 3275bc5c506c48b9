"""Flash attention Pallas kernels vs the jnp oracle (interpret mode).

Follows the reference's kernel-test pattern (fuzz over odd sizes and
option cross products vs a pure reference, e.g.
``tests/L0/run_amp/test_multi_tensor_scale.py``).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import importlib

from apex_tpu.ops.flash_attention import (
    FLASH_AUTO_MIN_SEQ,
    _auto_use_pallas,
    _reference,
    block_kinds,
    flash_attention,
    make_flash_attention,
)

# the package exports the function under the module's own name
fa = importlib.import_module("apex_tpu.ops.flash_attention")

BQ = BK = 32  # small blocks so tiny shapes exercise multi-block grids


def _qkv(b, s, h, d, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) for k in ks)


def _flash(q, k, v, **kw):
    return flash_attention(q, k, v, use_pallas=True, interpret=True,
                           block_q=BQ, block_k=BK, **kw)


@pytest.mark.parametrize("s", [32, 64, 100, 33])  # exact, multiple, ragged
@pytest.mark.parametrize("causal", [False, True])
def test_matches_reference(s, causal):
    q, k, v = _qkv(2, s, 2, 16, seed=s)
    got = _flash(q, k, v, causal=causal)
    want = _reference(q, k, v, None, causal, 1.0 / math.sqrt(16))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_key_mask_and_fully_masked_rows():
    s = 64
    q, k, v = _qkv(2, s, 2, 16, seed=1)
    kv_mask = jnp.broadcast_to(
        jnp.where(jnp.arange(s)[None] < s - 9, 0.0, -1e30), (2, s))
    kv_mask = kv_mask.at[1].set(-1e30)  # batch row 1 fully masked
    got = np.asarray(_flash(q, k, v, kv_mask=kv_mask))
    want = np.asarray(_reference(q, k, v, kv_mask, False,
                                 1.0 / math.sqrt(16)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert np.all(got[1] == 0.0)
    # masked keys must not influence the output
    got2 = np.asarray(_flash(q, k, v.at[:, s - 4:].set(77.0),
                             kv_mask=kv_mask))
    np.testing.assert_allclose(got, got2, rtol=1e-6, atol=1e-6)


def test_cross_attention_lengths():
    q, _, _ = _qkv(2, 48, 2, 16, seed=2)
    _, k, v = _qkv(2, 80, 2, 16, seed=3)
    got = _flash(q, k, v)
    want = _reference(q, k, v, None, False, 1.0 / math.sqrt(16))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_gradients_fully_masked_rows_are_zero():
    """Backward for an all-masked batch row must be exactly zero — the
    recompute path p = exp(s - lse) evaluates to 1 there without an
    explicit guard (review regression)."""
    s = 64
    q, k, v = _qkv(2, s, 2, 16, seed=11)
    kv_mask = jnp.zeros((2, s)).at[1].set(-1e30)

    def lf(q, k, v):
        return jnp.sum(_flash(q, k, v, kv_mask=kv_mask)
                       .astype(jnp.float32) ** 2)

    dq, dk, dv = jax.grad(lf, (0, 1, 2))(q, k, v)
    assert np.all(np.asarray(dq)[1] == 0.0)
    assert np.all(np.asarray(dk)[1] == 0.0)
    assert np.all(np.asarray(dv)[1] == 0.0)
    # the live row still gets correct gradients
    def lr(q, k, v):
        return jnp.sum(_reference(q, k, v, kv_mask, False,
                                  1.0 / math.sqrt(16))
                       .astype(jnp.float32) ** 2)
    gr = jax.grad(lr, (0, 1, 2))(q, k, v)
    for a, b in zip((dq, dk, dv), gr):
        np.testing.assert_allclose(np.asarray(a)[0], np.asarray(b)[0],
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(causal):
    s = 64
    q, k, v = _qkv(2, s, 2, 16, seed=4)
    kv_mask = jnp.broadcast_to(
        jnp.where(jnp.arange(s)[None] < s - 7, 0.0, -1e30), (2, s))

    def lf(q, k, v):
        return jnp.sum(_flash(q, k, v, kv_mask=kv_mask, causal=causal)
                       .astype(jnp.float32) ** 2)

    def lr(q, k, v):
        return jnp.sum(_reference(q, k, v, kv_mask, causal,
                                  1.0 / math.sqrt(16))
                       .astype(jnp.float32) ** 2)

    gf = jax.grad(lf, (0, 1, 2))(q, k, v)
    gr = jax.grad(lr, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def _loss_grads(fn, q, k, v, w):
    """Output and the three gradients of ``sum(fn(q, k, v) * w)``."""
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * w)
    return (fn(q, k, v),) + jax.grad(loss, (0, 1, 2))(q, k, v)


def _f32(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("case", ["plain", "key_mask", "dropout"])
@pytest.mark.parametrize("causal", [False, True])
def test_bf16_operands_fp32_accumulation(causal, case):
    """bfloat16 inputs: the products take bfloat16 operands (``p`` and
    ``ds`` are rounded to the inputs' dtype, as the inputs themselves
    were) and accumulate in float32.  Against the float32 reference on
    the SAME bfloat16 values the output is off by its own rounding to
    bfloat16: half a step of 2**-7, so 2**-8 of the largest value at
    most (read 0.0021 to 0.0037, where this test allowed 2e-2 of one
    while it was ``test_bf16_io_fp32_math``).  A gradient carries that
    rounding and those of ``p``, ``ds`` and the ``c * p`` of dropout on
    its way: four of them, 2**-6 of its largest entry (read 0.0029 to
    0.0087)."""
    s = 100
    q, k, v = _qkv(2, s, 2, 16, seed=5, dtype=jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(6), (2, s, 2, 16), jnp.float32)
    kw = dict(causal=causal)
    if case == "key_mask":
        kw["kv_mask"] = jnp.broadcast_to(
            jnp.where(jnp.arange(s)[None] < s - 9, 0.0, -1e30), (2, s))
    if case == "dropout":
        kw.update(dropout_rate=0.25, dropout_seed=3)
    got = _loss_grads(lambda q, k, v: _flash(q, k, v, **kw), q, k, v, w)
    want = _loss_grads(
        lambda q, k, v: flash_attention(q, k, v, use_pallas=False, **kw),
        *(x.astype(jnp.float32) for x in (q, k, v)), w)
    assert all(g.dtype == jnp.bfloat16 for g in got)
    for name, g, r, ulps in zip(("out", "dq", "dk", "dv"), got, want,
                                (2 ** -8, 2 ** -6, 2 ** -6, 2 ** -6)):
        gap = np.abs(_f32(g) - _f32(r)).max() / np.abs(_f32(r)).max()
        assert gap < ulps, (name, gap)


def _kernel_dots(fn, *args):
    """``(lhs dtype, rhs dtype)`` of every ``dot_general`` inside the
    three ``pallas_call``s of ``fn``'s jaxpr, by kernel name."""
    found = {}

    def walk(jaxpr, kernel):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general" and kernel:
                found.setdefault(kernel, []).append(
                    tuple(v.aval.dtype.name for v in eqn.invars))
            inner = kernel
            if eqn.primitive.name == "pallas_call":
                inner = eqn.params["name"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
    return found


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("causal", [False, True])
def test_products_run_at_the_inputs_width(dtype, causal):
    """The operand width follows what the code observes, the inputs'
    dtype: no float32 x float32 product for bfloat16 inputs (nine
    products, every one bfloat16 x bfloat16), none but float32 for
    float32 inputs (amp O0, the parity tests)."""
    q, k, v = _qkv(1, 64, 2, 16, seed=7, dtype=jnp.dtype(dtype))
    dots = _kernel_dots(
        jax.grad(lambda q, k, v: jnp.sum(_flash(q, k, v, causal=causal)
                                         .astype(jnp.float32)), (0, 1, 2)),
        q, k, v)
    assert set(dots) == {"_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel"}
    kinds = 2 if causal else 1      # a masked and an unmasked branch
    assert {n: len(d) for n, d in dots.items()} == {
        "_fwd_kernel": 2 * kinds, "_bwd_dq_kernel": 3 * kinds,
        "_bwd_dkv_kernel": 4 * kinds}
    assert {pair for d in dots.values() for pair in d} == {(dtype, dtype)}


def _mask_every_block(monkeypatch):
    """The kernels as they were before the three kinds: every block the
    diagonal reaches is computed whole under the causal mask."""
    def reaches_only(iq, ik, bq, bk):
        reaches = iq * bq + bq - 1 >= ik * bk
        return reaches, reaches & False

    monkeypatch.setattr(fa, "_block_kind", reaches_only)
    monkeypatch.setattr(fa, "_strips",
                        lambda masked, bq, bk, by: ((0, bq, 0, bk),))
    jax.clear_caches()


@pytest.mark.parametrize("sq,sk,block", [
    (33, 33, 128),       # one padded block: the diagonal inside it
    (100, 100, 128),
    (256, 256, 128),     # the diagonal on the blocks' edges
    (256, 256, 256),
    (1024, 1024, 128),   # 36 blocks skipped, 8 masked, 28 unmasked
    (1024, 1024, 256),
    (300, 300, 128),     # padded keys in the last block
    (200, 456, 128),     # sq != sk: local block indices decide
    (456, 200, 256),
])
def test_three_kinds_of_block_are_bit_identical_to_masking_every_block(
        sq, sk, block, monkeypatch):
    """Skipping the mask below the diagonal changes no bit: ``where``
    over an all-true compare returns its first operand.  Forward, lse
    and the three gradients, float32 so that nothing hides in a
    rounding."""
    q, _, _ = _qkv(1, sq, 1, 16, seed=sq)
    _, k, v = _qkv(1, sk, 1, 16, seed=sk + 1)

    def run():
        def loss(q, k, v):
            out, lse = flash_attention(
                q, k, v, causal=True, use_pallas=True, interpret=True,
                block_q=block, block_k=block, return_lse=True)
            return jnp.sum(out ** 2) + jnp.sum(
                jnp.where(lse > -1e29, lse, 0.0)), (out, lse)
        grads, (out, lse) = jax.grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
        return [np.asarray(x) for x in (out, lse, *grads)]

    kinds = run()
    _mask_every_block(monkeypatch)
    every = run()
    monkeypatch.undo()
    jax.clear_caches()
    for a, b in zip(kinds, every):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("case", ["plain", "key_mask", "dropout"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_strips_of_a_diagonal_block(case, dtype, monkeypatch):
    """The backward kernels compute a square block on the diagonal
    strip by strip, each against the keys (queries, in the dkv kernel)
    it can see; the sums then run over fewer terms in another order, so
    the gradients agree with the whole masked block to rounding, not to
    the bit.  The forward keeps the block whole."""
    s, block = 200, 64
    q, k, v = _qkv(1, s, 2, 16, seed=8, dtype=jnp.dtype(dtype))
    w = jax.random.normal(jax.random.PRNGKey(9), (1, s, 2, 16), jnp.float32)
    kw = dict(causal=True, use_pallas=True, interpret=True, block_q=block,
              block_k=block)
    if case == "key_mask":
        kw["kv_mask"] = jnp.where(jnp.arange(s)[None] < s - 30, 0.0, -1e30)
    if case == "dropout":
        kw.update(dropout_rate=0.25, dropout_seed=3)
    monkeypatch.setattr(fa, "_STRIP", 16)
    assert fa._strips(True, block, block, None) == ((0, 64, 0, 64),)
    assert fa._strips(True, block, block, "q") == (
        (0, 16, 0, 16), (16, 32, 0, 32), (32, 48, 0, 48), (48, 64, 0, 64))
    assert fa._strips(True, block, block, "k") == (
        (0, 64, 0, 16), (16, 64, 16, 32), (32, 64, 32, 48),
        (48, 64, 48, 64))
    assert fa._strips(False, block, block, "q") == ((0, 64, 0, 64),)
    jax.clear_caches()
    strips = _loss_grads(lambda q, k, v: flash_attention(q, k, v, **kw),
                         q, k, v, w)
    _mask_every_block(monkeypatch)
    whole = _loss_grads(lambda q, k, v: flash_attention(q, k, v, **kw),
                        q, k, v, w)
    monkeypatch.undo()
    jax.clear_caches()
    tol = 2e-6 if dtype == "float32" else 2 ** -7
    for a, b in zip(strips, whole):
        np.testing.assert_allclose(_f32(a), _f32(b), rtol=0,
                                   atol=tol * np.abs(_f32(b)).max())


def _kinds_by_position(sq, sk, bq, bk):
    """The same count from the positions themselves: a block is skipped
    where no query of it sees a key of it, unmasked where all see all."""
    counts = {"skipped": 0, "masked": 0, "unmasked": 0}
    for q0 in range(0, sq, bq):
        for k0 in range(0, sk, bk):
            sees = (np.arange(q0, q0 + bq)[:, None]
                    >= np.arange(k0, k0 + bk)[None, :])
            counts["unmasked" if sees.all() else
                   "masked" if sees.any() else "skipped"] += 1
    return counts


@pytest.mark.parametrize("sq,sk,bq,bk,want", [
    (1024, 1024, 256, 256, (6, 4, 6)),     # the training cell at 256
    (1024, 1024, 512, 512, (1, 2, 1)),
    (1024, 1024, 1024, 1024, (0, 1, 0)),
    (1024, 1024, 128, 128, (28, 8, 28)),
    (1024, 1024, 256, 512, (2, 4, 2)),
    (1024, 1024, 512, 256, (2, 4, 2)),
    (2048, 2048, 512, 1024, (2, 4, 2)),
    (4096, 4096, 1024, 1024, (6, 4, 6)),
    (256, 1024, 128, 128, (13, 2, 1)),     # sq != sk
    (1024, 256, 128, 128, (1, 2, 13)),
    (33, 33, 128, 128, (0, 1, 0)),         # one padded block
    (300, 300, 128, 128, (3, 3, 3)),
    (128, 128, 128, 1, (0, 127, 1)),       # a key a block
])
def test_block_kinds_counts(sq, sk, bq, bk, want):
    got = block_kinds(sq, sk, bq, bk, causal=True)
    assert (got["skipped"], got["masked"], got["unmasked"]) == want
    assert got == _kinds_by_position(sq, sk, bq, bk)
    n = -(-sq // bq) * -(-sk // bk)
    assert block_kinds(sq, sk, bq, bk, causal=False) == {
        "skipped": 0, "masked": 0, "unmasked": n}


def test_adapter_in_bert():
    from apex_tpu import models

    cfg = models.BertConfig(vocab_size=64, hidden_size=32,
                            num_hidden_layers=1, num_attention_heads=2,
                            intermediate_size=64,
                            max_position_embeddings=64,
                            hidden_dropout_prob=0.0,
                            attention_probs_dropout_prob=0.0)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 64)
    mask = jnp.ones((2, 64), jnp.int32).at[:, 50:].set(0)
    plain = models.BertEncoder(cfg)
    flash = models.BertEncoder(cfg, attention_fn=make_flash_attention(
        use_pallas=True, interpret=True, block_q=BQ, block_k=BK))
    variables = plain.init(jax.random.PRNGKey(1), ids, mask)
    want = plain.apply(variables, ids, mask)
    got = flash.apply(variables, ids, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


class TestAutoPathDecisionTable:
    """The use_pallas=None TPU auto path routes short sequences to XLA
    attention (BENCH_NOTES r5: flash LOSES at s128/s512 inside BERT,
    wins past 512 and at 16k).  The decision is a pure function pinned
    here shape-for-shape so a threshold change is a deliberate edit,
    not drift."""

    def test_threshold_value_pinned(self):
        assert FLASH_AUTO_MIN_SEQ == 512

    @pytest.mark.parametrize("sq,sk,want", [
        (128, 128, False),     # BERT-base s128: XLA 0.532 vs flash 0.392
        (512, 512, False),     # s512: XLA at best ties; stay on XLA
        (513, 513, True),      # strictly past the crossover
        (1024, 1024, True),    # gpt s1024 causal: flash 1.81x
        (16384, 16384, True),  # the long-context leg flash exists for
        (1, 1, False),
        # cross-attention: the LONGER side decides (the score tensor
        # is Sq x Sk; one long side already blows the XLA fusion)
        (128, 1024, True),
        (1024, 128, True),
        (128, 512, False),
    ])
    def test_seq_length_table(self, sq, sk, want):
        assert _auto_use_pallas(sq, sk) is want

    def test_dropout_always_takes_the_kernel(self):
        # in-kernel dropout avoids the (Sq, Sk) probs tensor in HBM
        # at ANY length — memory, not throughput, decides
        assert _auto_use_pallas(128, 128, dropout_rate=0.1) is True
        assert _auto_use_pallas(16, 16, dropout_rate=0.5) is True
        assert _auto_use_pallas(128, 128, dropout_rate=0.0) is False

    def test_explicit_use_pallas_bypasses_threshold(self):
        """use_pallas=True at a short length still runs the kernel
        (every parity test in this file relies on that)."""
        q, k, v = _qkv(1, 64, 2, 16, seed=9)
        got = flash_attention(q, k, v, use_pallas=True, interpret=True,
                              block_q=BQ, block_k=BK)
        want = _reference(q, k, v, None, False, 1.0 / math.sqrt(16))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_adapter_rejects_bad_bias_and_dropout():
    fn = make_flash_attention()
    q = jnp.ones((1, 32, 2, 16))
    with pytest.raises(ValueError, match="key-position-only"):
        fn(q, q, q, bias=jnp.zeros((1, 2, 32, 32)))
    # a bare probs->probs closure (no rate/seed annotation) cannot run
    # in-kernel; the message must point at the annotation contract
    with pytest.raises(NotImplementedError, match="rate"):
        fn(q, q, q, dropout_fn=lambda p: p)


class TestDropout:
    """In-kernel attention-probability dropout: the keep-mask is a
    deterministic hash of (seed, batch*head, q, k) regenerated
    identically in the forward kernel, both backward kernels, and the
    jnp oracle — so kernel-vs-oracle parity holds exactly at any fixed
    (rate, seed), and the VJP's dropped entries match the forward's."""

    B, S, H, D = 2, 64, 2, 32
    KW = dict(use_pallas=True, interpret=True, block_q=32, block_k=32)

    def _qkv(self, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        return tuple(jax.random.normal(k, (self.B, self.S, self.H, self.D))
                     for k in ks)

    @pytest.mark.parametrize("causal", [False, True])
    def test_kernel_matches_oracle(self, causal):
        q, k, v = self._qkv()
        o_pal = flash_attention(q, k, v, causal=causal, dropout_rate=0.3,
                                dropout_seed=7, **self.KW)
        o_ref = flash_attention(q, k, v, causal=causal, dropout_rate=0.3,
                                dropout_seed=7, use_pallas=False)
        np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                                   atol=2e-6)

    def test_gradients_match_oracle(self):
        q, k, v = self._qkv(1)

        def loss(fn_kwargs):
            def f(q, k, v):
                return flash_attention(
                    q, k, v, dropout_rate=0.3, dropout_seed=11,
                    **fn_kwargs).astype(jnp.float32).sum()
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        gp = loss(self.KW)
        gr = loss(dict(use_pallas=False))
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-6)

    def test_deterministic_and_seed_varying(self):
        q, k, v = self._qkv(2)
        kw = dict(dropout_rate=0.3, **self.KW)
        a = flash_attention(q, k, v, dropout_seed=5, **kw)
        b = flash_attention(q, k, v, dropout_seed=5, **kw)
        c = flash_attention(q, k, v, dropout_seed=6, **kw)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_rate_zero_equals_no_dropout(self):
        q, k, v = self._qkv(3)
        a = flash_attention(q, k, v, dropout_rate=0.0, **self.KW)
        b = flash_attention(q, k, v, **self.KW)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_drop_fraction_near_rate(self):
        from apex_tpu.ops.flash_attention import _dropout_keep
        bh = jnp.arange(8)[:, None, None]
        rows = jnp.arange(128)[None, :, None]
        cols = jnp.arange(128)[None, None, :]
        for rate in (0.1, 0.5):
            keep = _dropout_keep(jnp.int32(3), bh, rows, cols, rate)
            assert abs(float(1.0 - keep.mean()) - rate) < 0.01

    def test_requires_seed(self):
        q, k, v = self._qkv(4)
        with pytest.raises(ValueError, match="dropout_seed"):
            flash_attention(q, k, v, dropout_rate=0.3, **self.KW)

    def test_block_size_invariance(self):
        """The mask hashes GLOBAL coordinates, so the dropout pattern is
        independent of the VMEM tiling."""
        q, k, v = self._qkv(5)
        a = flash_attention(q, k, v, dropout_rate=0.3, dropout_seed=9,
                            use_pallas=True, interpret=True,
                            block_q=32, block_k=32)
        b = flash_attention(q, k, v, dropout_rate=0.3, dropout_seed=9,
                            use_pallas=True, interpret=True,
                            block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)

    def test_bert_default_config_on_flash_path(self):
        """The default BertConfig (attention dropout 0.1) trains on the
        fused path — the gap the round-2 review flagged (the adapter
        used to raise on any dropout_fn)."""
        import optax

        from apex_tpu import amp, models
        from apex_tpu.ops.flash_attention import make_flash_attention

        cfg = models.BertConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=32)
        assert cfg.attention_probs_dropout_prob == 0.1  # the default
        model, optimizer = amp.initialize(
            models.BertForPreTraining(cfg, attention_fn=make_flash_attention(
                **self.KW)),
            optax.adam(1e-3), opt_level="O2", verbosity=0)
        ids = jax.random.randint(jax.random.PRNGKey(0), (4, 32), 0, 64)
        labels = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 64)
        params = model.init(jax.random.PRNGKey(2), ids)["params"]
        opt_state = optimizer.init(params)

        @jax.jit
        def step(params, opt_state, rng):
            def loss_fn(p):
                mlm, _ = model.apply({"params": p}, ids,
                                     deterministic=False,
                                     rngs={"dropout": rng})
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    mlm.astype(jnp.float32), labels).mean()
                with amp.scale_loss(loss, opt_state) as scaled:
                    return scaled, loss
            grads, loss = jax.grad(loss_fn, has_aux=True)(params)
            params, opt_state = optimizer.step(params, grads, opt_state)
            return params, opt_state, loss

        rng = jax.random.PRNGKey(3)
        losses = []
        for _ in range(5):
            rng, sub = jax.random.split(rng)
            params, opt_state, loss = step(params, opt_state, sub)
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]



def test_sweep_prints_the_kinds_and_no_time_without_a_chip(capsys):
    """``tools/perf_sweep.py::sweep_flash`` takes the shape and the grid,
    walks the three kernels apart and prints ``block_kinds`` beside each
    point; its time is the kernel's on the DEVICE's clock, so on the CPU
    a point carries an error and no number."""
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
    rows = perf_sweep.sweep_flash((1, 256, 1, 16), blocks=(128, 512),
                                  iters=1, kernels=("fwd", "dkv"))
    assert [(r["kernel"], r["block_q"], r["block_k"]) for r in rows] == [
        ("fwd", 128, 128), ("dkv", 128, 128)]     # 512 does not divide 256
    for r in rows:
        assert (r["skipped"], r["masked"], r["unmasked"]) == (1, 2, 1)
        assert "ms" not in r and "mxu_peak_pct" not in r and "error" in r
    printed = [json.loads(l) for l in capsys.readouterr().out.splitlines()
               if l.startswith("{")]
    assert printed == rows
