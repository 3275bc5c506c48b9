"""GPipe pipeline parallelism: the scheduled, ppermute-hopping pipeline
must compute exactly what sequentially applying the stages computes —
forward and backward — and compose with data parallelism and training.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu import parallel

NDEV = 8
S = 4          # pipeline stages
B, F = 16, 12  # batch, feature


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.asarray(jax.devices()[:S]), ("pipe",))


def stage_fn(p, x):
    """One residual MLP stage; activation shape preserved (GPipe
    contract)."""
    return x + jnp.tanh(x @ p["w"] + p["b"])


def _stacked_params(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), S)
    w = jax.vmap(lambda k: jax.random.normal(k, (F, F)) * 0.3)(ks)
    b = jnp.zeros((S, F))
    return {"w": w, "b": b}


def _sequential(params, x):
    for i in range(S):
        x = stage_fn(jax.tree.map(lambda a: a[i], params), x)
    return x


def _x(seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, F))


@pytest.mark.parametrize("m", [1, 2, 4, 8])
def test_forward_matches_sequential(mesh, m):
    params, x = _stacked_params(), _x()
    got = jax.jit(lambda p, x: parallel.pipeline_apply(
        mesh, "pipe", stage_fn, p, x, num_microbatches=m))(params, x)
    want = _sequential(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_gradients_match_sequential(mesh):
    params, x = _stacked_params(), _x(2)
    tgt = _x(3)

    def pp_loss(p):
        y = parallel.pipeline_apply(mesh, "pipe", stage_fn, p, x,
                                    num_microbatches=4)
        return jnp.mean((y - tgt) ** 2)

    def seq_loss(p):
        return jnp.mean((_sequential(p, x) - tgt) ** 2)

    g_pp = jax.jit(jax.grad(pp_loss))(params)
    g_seq = jax.grad(seq_loss)(params)
    for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_training_descends_and_keeps_placement(mesh):
    params, x = _stacked_params(5), _x(6)
    tgt = jnp.sin(x * 2.0)
    tx = optax.adam(1e-2)
    params = jax.device_put(
        params, jax.tree.map(lambda _: NamedSharding(mesh, P("pipe")),
                             params))
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            y = parallel.pipeline_apply(mesh, "pipe", stage_fn, p, x,
                                        num_microbatches=4)
            return jnp.mean((y - tgt) ** 2)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < 0.7 * losses[0], losses
    assert params["w"].sharding.spec[0] == "pipe"


def test_dp_x_pp_composition():
    """(data, pipe) mesh: each data shard runs the pipeline on its half
    of every microbatch; result equals the sequential stack."""
    mesh = Mesh(np.asarray(jax.devices()[:NDEV]).reshape(2, S),
                ("data", "pipe"))
    params, x = _stacked_params(7), _x(8)
    run = parallel.gpipe_spmd(stage_fn, "pipe", num_microbatches=4)
    f = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P("pipe"), params),
                  P("data")),
        out_specs=P("data")))
    got = f(params, x)
    want = _sequential(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_stage_count_mismatch_raises(mesh):
    """8 stacked stages on a 4-wide axis would silently run only every
    2nd stage without the guard — must raise instead."""
    ks = jax.random.split(jax.random.PRNGKey(9), 2 * S)
    params = {"w": jax.vmap(
        lambda k: jax.random.normal(k, (F, F)) * 0.3)(ks),
        "b": jnp.zeros((2 * S, F))}
    with pytest.raises(ValueError, match="stage count must equal"):
        parallel.pipeline_apply(mesh, "pipe", stage_fn, params, _x(),
                                num_microbatches=4)


def _monolithic_params(variables, pp, layers_per_stage):
    """Rebuild the monolithic BertForPreTraining param tree from
    PipelinedBert's stacked-stage variables (same weights) — the oracle
    used by every pipelined-vs-sequential comparison."""
    sp = variables["params"]
    enc = dict(sp["embed"])
    for st in range(pp):
        for li in range(layers_per_stage):
            enc[f"layer_{st * layers_per_stage + li}"] = jax.tree.map(
                lambda a: a[st], sp["stages"][f"layer_{li}"])
    return {"encoder": enc, **sp["heads"]}


def test_pipelined_bert_matches_sequential():
    """PipelinedBert on a (data, pipe) mesh computes exactly what the
    monolithic BertForPreTraining computes with the same weights —
    embeddings/heads replicated, encoder stages pipelined, attention
    bias riding the activation pytree."""
    from apex_tpu import models

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "pipe"))
    cfg = models.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    pb = models.PipelinedBert(cfg, mesh, pp=4, num_microbatches=2,
                              batch_axis="data")
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 64)
    # ragged mask: last 4 positions padded out, so the bias actually
    # masks something through every stage
    mask = jnp.asarray(np.pad(np.ones((4, 12)), ((0, 0), (0, 4))),
                       jnp.int32)
    variables = pb.init(jax.random.PRNGKey(1), ids, mask)

    params = jax.device_put(variables["params"], jax.tree.map(
        lambda _: NamedSharding(mesh, P()), variables["params"]))
    params["stages"] = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P("pipe"))),
        variables["params"]["stages"])
    with mesh:
        mlm, nsp = jax.jit(lambda v, i, m: pb.apply(v, i, m))(
            {"params": params}, ids, mask)

    # sequential oracle with the SAME weights: stage layers unstacked
    # into encoder/layer_i, embed/head names match by construction
    seq_params = _monolithic_params(
        variables, 4, cfg.num_hidden_layers // 4)
    mlm_ref, nsp_ref = jax.jit(
        lambda p, i, m: models.BertForPreTraining(cfg).apply(
            {"params": p}, i, m, deterministic=True))(seq_params, ids, mask)
    np.testing.assert_allclose(np.asarray(mlm), np.asarray(mlm_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(nsp), np.asarray(nsp_ref),
                               rtol=1e-5, atol=1e-5)


def test_pipelined_bert_gradients_match_sequential():
    """Backward through the pytree-activation pipeline (per-leaf
    ppermute/psum in tick and collect) produces the SAME gradients as
    the monolithic model — per stage layer, per embed table, per head."""
    from apex_tpu import models

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("pipe",))
    cfg = models.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    pb = models.PipelinedBert(cfg, mesh, pp=4, num_microbatches=2)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 64)
    mask = jnp.asarray(np.pad(np.ones((4, 12)), ((0, 0), (0, 4))),
                       jnp.int32)
    labels = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, 64)
    variables = pb.init(jax.random.PRNGKey(1), ids, mask)

    def pp_loss(p):
        mlm, nsp = pb.apply({"params": p}, ids, mask)
        return optax.softmax_cross_entropy_with_integer_labels(
            mlm, labels).mean() + nsp.sum() * 1e-3

    with mesh:
        g_pp = jax.jit(jax.grad(pp_loss))(variables["params"])

    # sequential oracle, same weights
    sp = variables["params"]
    seq_params = _monolithic_params(variables, 4, 1)
    seq_model = models.BertForPreTraining(cfg)

    def seq_loss(p):
        mlm, nsp = seq_model.apply({"params": p}, ids, mask,
                                   deterministic=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            mlm, labels).mean() + nsp.sum() * 1e-3

    g_seq = jax.jit(jax.grad(seq_loss))(seq_params)

    tol = dict(rtol=1e-4, atol=1e-6)
    # embed tables (ride OUTSIDE the pipeline, grads via the stage-0 path)
    for k in sp["embed"]:
        np.testing.assert_allclose(
            np.asarray(jax.tree.leaves(g_pp["embed"][k])[0]),
            np.asarray(jax.tree.leaves(g_seq["encoder"][k])[0]),
            err_msg=f"embed/{k}", **tol)
    # per-stage layer grads == per-layer grads of the sequential model
    for st in range(4):
        got = jax.tree.map(lambda a: a[st], g_pp["stages"]["layer_0"])
        want = g_seq["encoder"][f"layer_{st}"]
        for gl, wl in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(gl), np.asarray(wl),
                                       err_msg=f"stage {st}", **tol)
    # heads
    for k in sp["heads"]:
        for gl, wl in zip(jax.tree.leaves(g_pp["heads"][k]),
                          jax.tree.leaves(g_seq[k])):
            np.testing.assert_allclose(np.asarray(gl), np.asarray(wl),
                                       err_msg=f"heads/{k}", **tol)


def test_lamb_per_slice_trust_ratio_matches_unstacked():
    """FusedLAMB(per_slice_trust_ratio=...): a (S, ...) stacked param
    updates exactly like S separate per-layer leaves — LAMB's layer-wise
    adaptation is preserved under PipelinedBert's stacked layout."""
    from apex_tpu import optimizers

    S_, F_ = 4, 8
    k = jax.random.PRNGKey(0)
    w = jax.random.normal(k, (S_, F_, F_))
    g = jax.random.normal(jax.random.PRNGKey(1), (S_, F_, F_))

    stacked_opt = optimizers.FusedLAMB(
        lr=1e-2, per_slice_trust_ratio=lambda path: True)
    st = stacked_opt.init({"stages": {"w": w}})
    new_stacked, _ = stacked_opt.step({"stages": {"w": w}},
                                      {"stages": {"w": g}}, st)

    unstacked_opt = optimizers.FusedLAMB(lr=1e-2)
    params_u = {f"layer_{i}": {"w": w[i]} for i in range(S_)}
    grads_u = {f"layer_{i}": {"w": g[i]} for i in range(S_)}
    new_u, _ = unstacked_opt.step(params_u, grads_u,
                                  unstacked_opt.init(params_u))

    for i in range(S_):
        np.testing.assert_allclose(
            np.asarray(new_stacked["stages"]["w"][i]),
            np.asarray(new_u[f"layer_{i}"]["w"]), rtol=1e-6, atol=1e-7)


def test_pipelined_bert_amp_train_step():
    """dp x pp BERT training: amp O2 + FusedLAMB over the pipelined
    model — loss descends, stage placement survives the update."""
    import functools

    from apex_tpu import amp, models, optimizers

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "pipe"))
    cfg = models.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    pb = models.PipelinedBert(cfg, mesh, pp=4, num_microbatches=2,
                              batch_axis="data")
    model, optimizer = amp.initialize(
        pb, optimizers.FusedLAMB(lr=1e-3), opt_level="O2", verbosity=0)
    ids = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 64)
    labels = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    variables = model.init(jax.random.PRNGKey(2), ids)
    params = variables["params"]
    params["stages"] = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P("pipe"))),
        params["stages"])
    opt_state = optimizer.init(params)
    ids_s = jax.device_put(ids, NamedSharding(mesh, P("data")))
    lab_s = jax.device_put(labels, NamedSharding(mesh, P("data")))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, ids, labels):
        def loss_fn(p):
            mlm, _ = model.apply({"params": p}, ids)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                mlm.astype(jnp.float32), labels).mean()
            with amp.scale_loss(loss, opt_state) as scaled:
                return scaled, loss
        grads, loss = jax.grad(loss_fn, has_aux=True)(params)
        params, opt_state = optimizer.step(params, grads, opt_state)
        return params, opt_state, loss

    losses = []
    with mesh:
        for _ in range(6):
            params, opt_state, loss = step(params, opt_state, ids_s, lab_s)
            losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0]
    leaf = jax.tree.leaves(params["stages"])[0]
    assert leaf.sharding.spec[0] == "pipe"


def test_pipelined_bert_dropout():
    """DEFAULT dropout config under PP: per-(microbatch, stage) keys
    fold inside the pipeline body — training is stochastic per rng,
    deterministic per fixed rng, and eval ignores dropout entirely."""
    from apex_tpu import models

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("pipe",))
    cfg = models.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=16)  # default dropout probs 0.1
    pb = models.PipelinedBert(cfg, mesh, pp=4, num_microbatches=2)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 64)
    variables = pb.init(jax.random.PRNGKey(1), ids)

    with mesh:
        r1 = pb.apply(variables, ids, deterministic=False,
                      rngs={"dropout": jax.random.PRNGKey(7)})[0]
        r1b = pb.apply(variables, ids, deterministic=False,
                       rngs={"dropout": jax.random.PRNGKey(7)})[0]
        r2 = pb.apply(variables, ids, deterministic=False,
                      rngs={"dropout": jax.random.PRNGKey(8)})[0]
        ev1 = pb.apply(variables, ids, deterministic=True)[0]
        ev2 = pb.apply(variables, ids, deterministic=True)[0]
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r1b))
    assert not np.array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(ev1), np.asarray(ev2))
    assert not np.array_equal(np.asarray(r1), np.asarray(ev1))

    # missing rng is an actionable error, not silent determinism
    with mesh, pytest.raises(ValueError, match="dropout"):
        pb.apply(variables, ids, deterministic=False)

    # dp x pp: the batch_axis fold runs (keys also differ per data
    # shard) and the same determinism contract holds on the 2-axis mesh
    mesh2 = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                 ("data", "pipe"))
    pb2 = models.PipelinedBert(cfg, mesh2, pp=4, num_microbatches=2,
                               batch_axis="data")
    with mesh2:
        d1 = pb2.apply(variables, ids, deterministic=False,
                       rngs={"dropout": jax.random.PRNGKey(7)})[0]
        d1b = pb2.apply(variables, ids, deterministic=False,
                        rngs={"dropout": jax.random.PRNGKey(7)})[0]
        dev = pb2.apply(variables, ids, deterministic=True)[0]
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d1b))
    assert not np.array_equal(np.asarray(d1), np.asarray(dev))
    # eval equals the single-axis mesh's eval: placement-invariant
    np.testing.assert_allclose(np.asarray(dev), np.asarray(ev1),
                               rtol=1e-5, atol=1e-5)


def test_pipelined_bert_moe_aux_matches_monolithic():
    """MoE under PP: the aux accumulator riding the activation pytree
    reproduces the monolithic model's summed "losses" collection (same
    weights, deterministic), and a dp x pp MoE step trains."""
    import functools

    from apex_tpu import amp, models, optimizers

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("pipe",))
    cfg = models.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, moe_experts=4)
    pb = models.PipelinedBert(cfg, mesh, pp=4, num_microbatches=2)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 64)
    variables = pb.init(jax.random.PRNGKey(1), ids)

    with mesh:
        mlm, nsp, aux = pb.apply(variables, ids)
    assert np.isfinite(float(aux)) and float(aux) > 0

    # monolithic oracle with the SAME weights
    seq_params = _monolithic_params(variables, 4, 1)
    (mlm_ref, _), mut = models.BertForPreTraining(cfg).apply(
        {"params": seq_params}, ids, deterministic=True,
        mutable=["losses"])
    aux_ref = sum(jnp.sum(leaf) for leaf in
                  jax.tree_util.tree_leaves(mut["losses"]))
    np.testing.assert_allclose(np.asarray(mlm), np.asarray(mlm_ref),
                               rtol=1e-4, atol=1e-5)
    # PP averages per-microbatch aux estimates; with 2 microbatches of
    # the same distribution the value sits near the full-batch one
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=0.2)

    # dp x pp MoE training step with the aux in the loss
    mesh2 = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                 ("data", "pipe"))
    pb2 = models.PipelinedBert(cfg, mesh2, pp=4, num_microbatches=2,
                               batch_axis="data")
    model, optimizer = amp.initialize(
        pb2, optimizers.FusedLAMB(lr=1e-3), opt_level="O2", verbosity=0)
    labels = jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0, 64)
    ids8 = jax.random.randint(jax.random.PRNGKey(4), (8, 16), 0, 64)
    params = model.init(jax.random.PRNGKey(5), ids8)["params"]
    params["stages"] = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh2, P("pipe"))),
        params["stages"])
    opt_state = optimizer.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state):
        def loss_fn(p):
            mlm, _, aux = model.apply({"params": p}, ids8)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                mlm.astype(jnp.float32), labels).mean() + 0.01 * aux
            from apex_tpu import amp as _amp
            with _amp.scale_loss(loss, opt_state) as scaled:
                return scaled, loss
        grads, loss = jax.grad(loss_fn, has_aux=True)(params)
        params, opt_state = optimizer.step(params, grads, opt_state)
        return params, opt_state, loss

    with mesh2:
        losses = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state)
            losses.append(float(loss))
    assert all(np.isfinite(losses))


def test_pipelined_bert_dp_sp_pp():
    """The full dp x sp x pp composition on one (2, 2, 2) mesh: ring
    attention's collectives run INSIDE the pipeline body over the sp
    axis, and the result matches the monolithic full-attention model
    with the same weights."""
    from apex_tpu import models, parallel

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                ("data", "sp", "pipe"))
    cfg = models.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    ring = parallel.make_ring_attention("sp")
    pb = models.PipelinedBert(cfg, mesh, pp=2, num_microbatches=2,
                              batch_axis="data", seq_axis="sp",
                              attention_fn=ring)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 64)
    mask = jnp.asarray(np.pad(np.ones((4, 12)), ((0, 0), (0, 4))),
                       jnp.int32)
    variables = pb.init(jax.random.PRNGKey(1), ids, mask)
    with mesh:
        mlm, nsp = jax.jit(lambda v, i, m: pb.apply(v, i, m))(
            variables, ids, mask)

    # monolithic full-attention oracle, same weights
    seq_params = _monolithic_params(variables, 2, 1)
    mlm_ref, nsp_ref = models.BertForPreTraining(cfg).apply(
        {"params": seq_params}, ids, mask, deterministic=True)
    np.testing.assert_allclose(np.asarray(mlm), np.asarray(mlm_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(nsp), np.asarray(nsp_ref),
                               rtol=2e-4, atol=2e-4)


def test_pipelined_bert_seq_axis_requires_attention_fn():
    from apex_tpu import models

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                ("data", "sp", "pipe"))
    cfg = models.BertConfig(num_hidden_layers=2)
    with pytest.raises(ValueError, match="seq_axis"):
        models.PipelinedBert(cfg, mesh, pp=2, num_microbatches=2,
                             seq_axis="sp")


def test_pipelined_bert_dp_tp_pp():
    """dp x tp x pp: Megatron tensor parallelism runs INSIDE the
    pipeline via partial-manual shard_map (the model axis stays
    GSPMD-automatic, pipe/data explicit); stage weights carry
    P(pipe, ...model...) placement and the result matches the
    monolithic model exactly."""
    from apex_tpu import models

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                ("data", "model", "pipe"))
    cfg = models.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    pb = models.PipelinedBert(cfg, mesh, pp=2, num_microbatches=2,
                              batch_axis="data", tp_axis="model")
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 64)
    raw = pb.init(jax.random.PRNGKey(1), ids)
    variables = pb.shard_variables(raw)

    # Megatron placement landed on the stacked stage weights
    qk = variables["params"]["stages"]["layer_0"]["attention"]["query"][
        "kernel"]
    assert qk.sharding.spec == P("pipe", None, "model", None)
    inter = variables["params"]["stages"]["layer_0"]["intermediate"][
        "kernel"]
    assert inter.sharding.spec == P("pipe", None, "model")
    # embeddings/heads take their unstacked TP specs
    emb = variables["params"]["embed"]["word_embeddings"]["embedding"]
    assert emb.sharding.spec == P("model", None)

    with mesh:
        mlm, nsp = jax.jit(lambda v, i: pb.apply(v, i))(variables, ids)

    seq_params = _monolithic_params(raw, 2, 1)
    mlm_ref, nsp_ref = models.BertForPreTraining(cfg).apply(
        {"params": seq_params}, ids, deterministic=True)
    np.testing.assert_allclose(np.asarray(mlm), np.asarray(mlm_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(nsp), np.asarray(nsp_ref),
                               rtol=2e-5, atol=2e-5)


def test_pipelined_bert_dp_tp_pp_trains():
    """A FusedLAMB training step over the dp x tp x pp placement (fp32:
    bf16 compute inside the partial-manual shard_map trips an XLA
    CPU-backend crash in this jax build, see PipelinedBert docstring):
    loss descends and both the pipe and model shardings survive."""
    import functools

    from apex_tpu import models, optimizers

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                ("data", "model", "pipe"))
    cfg = models.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    pb = models.PipelinedBert(cfg, mesh, pp=2, num_microbatches=2,
                              batch_axis="data", tp_axis="model")
    optimizer = optimizers.FusedLAMB(lr=1e-3)
    ids = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 64)
    labels = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    variables = pb.shard_variables(pb.init(jax.random.PRNGKey(2), ids))
    params = variables["params"]
    opt_state = optimizer.init(params)
    ids_s = jax.device_put(ids, NamedSharding(mesh, P("data")))
    lab_s = jax.device_put(labels, NamedSharding(mesh, P("data")))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, ids, labels):
        def loss_fn(p):
            mlm, _ = pb.apply({"params": p}, ids)
            return optax.softmax_cross_entropy_with_integer_labels(
                mlm.astype(jnp.float32), labels).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = optimizer.step(params, grads, opt_state)
        return params, opt_state, loss

    losses = []
    with mesh:
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, ids_s,
                                           lab_s)
            losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    qk = params["stages"]["layer_0"]["attention"]["query"]["kernel"]
    assert "pipe" in qk.sharding.spec and "model" in str(qk.sharding.spec)


# ---------------------------------------------------------------- 1F1B


def _mse(y, t):
    return jnp.mean((y - t) ** 2)


def _seq_loss(params, x, tgt):
    return _mse(_sequential(params, x), tgt)


@pytest.mark.parametrize("m", [2, 4, 8])
def test_onef1b_matches_sequential(mesh, m):
    """The interleaved 1F1B schedule's loss, stage-param grads, AND
    input grads equal the sequential stack's autodiff exactly — for
    M < S (bubble-dominated), M == S, and M = 2S (ring-buffer slot
    reuse)."""
    params, x = _stacked_params(11), _x(12)
    tgt = _x(13)
    loss, grads, dx = jax.jit(
        lambda p, x, t: parallel.onef1b_loss_and_grad(
            mesh, "pipe", stage_fn, _mse, p, x, t,
            num_microbatches=m))(params, x, tgt)
    want_l, want_g = jax.value_and_grad(_seq_loss)(params, x, tgt)
    want_dx = jax.grad(_seq_loss, argnums=1)(params, x, tgt)
    np.testing.assert_allclose(float(loss), float(want_l), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(want_dx),
                               rtol=1e-5, atol=1e-6)


def test_onef1b_pytree_activations(mesh):
    """Side inputs ride the activation pytree through the interleaved
    schedule: (hidden, bias) stages with the bias returned unchanged,
    grads still exact vs sequential."""
    def stage2(p, xb):
        h, bias = xb
        return (h + jnp.tanh(h @ p["w"] + p["b"] + bias), bias)

    def seq2(params, xb):
        for i in range(S):
            xb = stage2(jax.tree.map(lambda a: a[i], params), xb)
        return xb[0]

    def loss2(yb, t):
        return jnp.mean((yb[0] - t) ** 2)

    params = _stacked_params(14)
    h, bias = _x(15), 0.1 * _x(16)
    tgt = _x(17)
    loss, grads, dxb = jax.jit(
        lambda p, xb, t: parallel.onef1b_loss_and_grad(
            mesh, "pipe", stage2, loss2, p, xb, t,
            num_microbatches=4))(params, (h, bias), tgt)

    def seq_l(p, xb):
        return jnp.mean((seq2(p, xb) - tgt) ** 2)

    want_l, want_g = jax.value_and_grad(seq_l)(params, (h, bias))
    want_dxb = jax.grad(seq_l, argnums=1)(params, (h, bias))
    np.testing.assert_allclose(float(loss), float(want_l), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(dxb), jax.tree.leaves(want_dxb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_onef1b_dp_x_pp_training():
    """(data, pipe) mesh: the 1F1B loss-and-grad drives a real training
    loop — the schedule returns per-data-shard PARTIAL grads (params
    are pvary'd so nothing reduces implicitly) and this wrapper pmeans
    them once; loss descends, placement preserved."""
    mesh = Mesh(np.asarray(jax.devices()[:NDEV]).reshape(2, S),
                ("data", "pipe"))
    params, x = _stacked_params(18), _x(19)
    tgt = jnp.sin(x * 2.0)
    tx = optax.adam(1e-2)
    params = jax.device_put(
        params, jax.tree.map(lambda _: NamedSharding(mesh, P("pipe")),
                             params))
    opt_state = tx.init(params)
    run = parallel.onef1b_spmd(stage_fn, _mse, "pipe",
                               num_microbatches=4)

    def spmd(p_local, x_local, t_local):
        loss, g, _ = run(p_local, x_local, t_local)
        return (jax.lax.pmean(loss, "data"),
                jax.tree.map(lambda a: jax.lax.pmean(a, "data"), g))

    smap = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P("pipe"), params),
                  P("data"), P("data")),
        out_specs=(P(), jax.tree.map(lambda _: P("pipe"), params)))

    @jax.jit
    def step(params, opt_state):
        loss, grads = smap(params, x, tgt)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < 0.7 * losses[0], losses
    assert params["w"].sharding.spec[0] == "pipe"


def _pretrain_loss(mlm, nsp, tgt):
    """Toy pretraining objective over both heads (mean over rows)."""
    oh = jax.nn.one_hot(tgt["mlm"], mlm.shape[-1])
    l1 = -jnp.mean(jnp.sum(jax.nn.log_softmax(mlm) * oh, -1))
    oh2 = jax.nn.one_hot(tgt["nsp"], 2)
    l2 = -jnp.mean(jnp.sum(jax.nn.log_softmax(nsp) * oh2, -1))
    return l1 + l2


def _bert_cfg(dropout=0.0):
    from apex_tpu import models
    return models.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=dropout,
        attention_probs_dropout_prob=0.0)


def _bert_batch(b=4, s=16):
    ids = jax.random.randint(jax.random.PRNGKey(0), (b, s), 0, 64)
    mask = jnp.asarray(np.pad(np.ones((b, s - 4)), ((0, 0), (0, 4))),
                       jnp.int32)
    tgt = {"mlm": jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, 64),
           "nsp": jax.random.randint(jax.random.PRNGKey(3), (b,), 0, 2)}
    return ids, mask, tgt


def test_bert_1f1b_matches_monolithic_grads():
    """loss_and_grad_1f1b == jax.value_and_grad of the monolithic
    BertForPreTraining with the same weights: loss, embedding grads
    (through the pipeline input cotangent), stage grads, head grads
    (through the schedule's differentiated loss_params)."""
    from apex_tpu import models

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("pipe",))
    cfg = _bert_cfg()
    pb = models.PipelinedBert(cfg, mesh, pp=4, num_microbatches=2)
    ids, mask, tgt = _bert_batch()
    variables = pb.init(jax.random.PRNGKey(1), ids, mask)

    loss, grads = jax.jit(
        lambda v, i, m, t: pb.loss_and_grad_1f1b(
            v, i, _pretrain_loss, t, attention_mask=m))(
        variables, ids, mask, tgt)

    seq_params = _monolithic_params(variables, 4,
                                    cfg.num_hidden_layers // 4)

    def mono_loss(p):
        mlm, nsp = models.BertForPreTraining(cfg).apply(
            {"params": p}, ids, mask, deterministic=True)
        return _pretrain_loss(mlm, nsp, tgt)

    want_l, want_g = jax.value_and_grad(mono_loss)(seq_params)
    np.testing.assert_allclose(float(loss), float(want_l), rtol=1e-5)
    # embeddings
    for k in grads["embed"]:
        for a, b in zip(jax.tree.leaves(grads["embed"][k]),
                        jax.tree.leaves(want_g["encoder"][k])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)
    # stage layers: stacked (pp, ...) vs encoder/layer_i
    for li in range(cfg.num_hidden_layers):
        got_li = jax.tree.map(lambda a: a[li],
                              grads["stages"]["layer_0"])
        for a, b in zip(jax.tree.leaves(got_li),
                        jax.tree.leaves(want_g["encoder"][f"layer_{li}"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)
    # heads
    for k in grads["heads"]:
        for a, b in zip(jax.tree.leaves(grads["heads"][k]),
                        jax.tree.leaves(want_g[k])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)


def test_bert_1f1b_dp_x_pp_matches_monolithic():
    """(data, pipe) composition: global-batch mean loss and grads equal
    the monolithic single-program autodiff (DDP semantics)."""
    from apex_tpu import models

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "pipe"))
    cfg = _bert_cfg()
    pb = models.PipelinedBert(cfg, mesh, pp=4, num_microbatches=2,
                              batch_axis="data")
    ids, mask, tgt = _bert_batch()
    variables = pb.init(jax.random.PRNGKey(1), ids, mask)
    loss, grads = jax.jit(
        lambda v, i, m, t: pb.loss_and_grad_1f1b(
            v, i, _pretrain_loss, t, attention_mask=m))(
        variables, ids, mask, tgt)

    seq_params = _monolithic_params(variables, 4,
                                    cfg.num_hidden_layers // 4)

    def mono_loss(p):
        mlm, nsp = models.BertForPreTraining(cfg).apply(
            {"params": p}, ids, mask, deterministic=True)
        return _pretrain_loss(mlm, nsp, tgt)

    want_l, want_g = jax.value_and_grad(mono_loss)(seq_params)
    np.testing.assert_allclose(float(loss), float(want_l), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads["heads"]),
                    jax.tree.leaves({k: want_g[k]
                                     for k in grads["heads"]})):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)
    for k in grads["embed"]:
        for a, b in zip(jax.tree.leaves(grads["embed"][k]),
                        jax.tree.leaves(want_g["encoder"][k])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)
    # STAGE grads under dp were the gap that hid a double-count (the
    # schedule's grads were data-psum'd by an implicit transpose
    # collective AND pmean'd by the wrapper, 2x); pin them per layer
    for li in range(cfg.num_hidden_layers):
        got_li = jax.tree.map(lambda a: a[li],
                              grads["stages"]["layer_0"])
        for a, b in zip(jax.tree.leaves(got_li),
                        jax.tree.leaves(want_g["encoder"][f"layer_{li}"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)


def test_bert_1f1b_dropout_matches_gpipe_autodiff():
    """With live dropout, 1F1B's rematerialized backward draws the SAME
    per-(microbatch, stage) keys as the GPipe apply path, so grads must
    match autodiff through apply exactly."""
    from apex_tpu import models

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("pipe",))
    cfg = _bert_cfg(dropout=0.1)
    pb = models.PipelinedBert(cfg, mesh, pp=4, num_microbatches=2)
    ids, mask, tgt = _bert_batch()
    variables = pb.init(jax.random.PRNGKey(1), ids, mask)
    key = jax.random.PRNGKey(7)

    loss, grads = jax.jit(
        lambda v, i, m, t: pb.loss_and_grad_1f1b(
            v, i, _pretrain_loss, t, attention_mask=m,
            deterministic=False, rngs={"dropout": key}))(
        variables, ids, mask, tgt)

    def gpipe_loss(p):
        mlm, nsp = pb.apply({"params": p}, ids, mask,
                            deterministic=False,
                            rngs={"dropout": key})
        return _pretrain_loss(mlm, nsp, tgt)

    want_l, want_g = jax.jit(jax.value_and_grad(gpipe_loss))(
        variables["params"])
    np.testing.assert_allclose(float(loss), float(want_l), rtol=1e-5)
    for name in ("embed", "stages", "heads"):
        for a, b in zip(jax.tree.leaves(grads[name]),
                        jax.tree.leaves(want_g[name])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)


def test_onef1b_memory_bounded(mesh):
    """The schedule's memory claim, pinned via XLA's memory analysis:
    GPipe-under-autodiff temp memory grows with the microbatch count
    (XLA saves every tick's activations), 1F1B's stays flat (ring
    buffer of S stage inputs + rematerialized backward). This test
    pins M=4 -> M=16 at constant microbatch size (gpipe ~2.9x growth,
    1f1b flat); a wider one-off probe on this backend measured gpipe
    2.4 -> 26 MB at M=4 -> 64 vs 1f1b flat at ~1 MB."""
    F2 = 256
    ks = jax.random.split(jax.random.PRNGKey(0), S)
    params = {"w": jax.vmap(
        lambda k: jax.random.normal(k, (F2, F2)) * 0.3)(ks),
        "b": jnp.zeros((S, F2))}
    mse = lambda y, t: jnp.mean((y - t) ** 2)

    def temp_bytes(fn, *args):
        ma = jax.jit(fn).lower(*args).compile().memory_analysis()
        if ma is None or not ma.temp_size_in_bytes:
            # backend without memory analysis (or temps folded into
            # aliased buffers): nothing meaningful to pin
            pytest.skip("backend reports no temp-memory analysis")
        return ma.temp_size_in_bytes

    sizes = {}
    for m in (4, 16):
        B2 = 64 * m  # microbatch size constant; only the count grows
        x = jax.random.normal(jax.random.PRNGKey(1), (B2, F2))
        tgt = jax.random.normal(jax.random.PRNGKey(2), (B2, F2))

        def gpipe_lg(p, x, t, m=m):
            return jax.value_and_grad(
                lambda p: mse(parallel.pipeline_apply(
                    mesh, "pipe", stage_fn, p, x,
                    num_microbatches=m), t))(p)

        def onef1b_lg(p, x, t, m=m):
            l, g, _ = parallel.onef1b_loss_and_grad(
                mesh, "pipe", stage_fn, mse, p, x, t,
                num_microbatches=m)
            return l, g

        sizes[m] = (temp_bytes(gpipe_lg, params, x, tgt),
                    temp_bytes(onef1b_lg, params, x, tgt))

    gpipe_growth = sizes[16][0] / sizes[4][0]
    onef1b_growth = sizes[16][1] / sizes[4][1]
    assert gpipe_growth > 2.0, sizes   # grows with M (measured ~2.9x)
    assert onef1b_growth < 1.5, sizes  # bounded by S (measured 1.0x)
    # and at M=16 the interleaved schedule uses several times less
    assert sizes[16][1] * 3 < sizes[16][0], sizes


def test_bert_1f1b_amp_o2_dots_bf16():
    """The amp passthrough (AmpModel.loss_and_grad_1f1b) keeps the
    schedule's matmuls on bf16 operands through forward AND the
    rematerialized backward — the perf pin the autodiff train paths
    have in tests/L0/test_norm_dtype_seam.py, for the manual-grad
    path."""
    from apex_tpu import amp, models

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("pipe",))
    cfg = _bert_cfg()
    pb = models.PipelinedBert(cfg, mesh, pp=4, num_microbatches=2)
    model = amp.initialize(pb, None, opt_level="O2", verbosity=0)
    ids, mask, tgt = _bert_batch()
    variables = pb.init(jax.random.PRNGKey(1), ids, mask)

    jaxpr = jax.make_jaxpr(
        lambda v, i, m, t: model.loss_and_grad_1f1b(
            v, i, _pretrain_loss, t, attention_mask=m))(
        variables, ids, mask, tgt)

    dots = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "dot_general":
                dots.append(tuple(v.aval.dtype.name
                                  for v in eqn.invars[:2]))
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):   # ClosedJaxpr (scan, pjit)
                    walk(v.jaxpr)
                elif hasattr(v, "eqns"):  # raw Jaxpr (shard_map)
                    walk(v)
                elif isinstance(v, (tuple, list)):
                    for u in v:           # cond stores `branches` as a
                        if hasattr(u, "jaxpr"):  # tuple of ClosedJaxprs
                            walk(u.jaxpr)
                        elif hasattr(u, "eqns"):
                            walk(u)

    walk(jaxpr.jaxpr)
    assert len(dots) > 10, f"only {len(dots)} dots traced — walker broken?"
    # fp32 dots are allowed only where amp policy demands them (loss
    # softmax path); every encoder/head matmul must be bf16 x bf16
    bf16 = [d for d in dots if d == ("bfloat16", "bfloat16")]
    f32 = [d for d in dots if d == ("float32", "float32")]
    assert len(bf16) >= len(dots) * 0.8, (
        f"amp O2 1F1B path off bf16: {len(bf16)}/{len(dots)} bf16 "
        f"(fp32: {len(f32)}, all: {sorted(set(dots))})")
    mixed = [d for d in dots if len(set(d)) > 1]
    assert not mixed, f"mixed-dtype dots (promotion seam): {mixed}"


@pytest.mark.parametrize("dispatch", ["dense", "capacity"])
def test_bert_1f1b_moe_matches_gpipe_autodiff(dispatch):
    """MoE under the interleaved schedule (dense and capacity dispatch,
    experts unsharded — the PipelinedBert regime where the stage body
    is collective-free): loss with the weighted aux and ALL grads —
    including router grads of EARLY stages, credited through the aux
    leaf's cotangent chain — match autodiff through the GPipe apply
    path, which slices the same microbatches."""
    from apex_tpu import models

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "pipe"))
    cfg = models.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, moe_experts=4,
        moe_dispatch=dispatch)
    pb = models.PipelinedBert(cfg, mesh, pp=4, num_microbatches=2,
                              batch_axis="data")
    ids, mask, tgt = _bert_batch()
    variables = pb.init(jax.random.PRNGKey(1), ids, mask)
    W = 0.01

    loss, grads = jax.jit(
        lambda v, i, m, t: pb.loss_and_grad_1f1b(
            v, i, _pretrain_loss, t, attention_mask=m,
            moe_aux_weight=W))(variables, ids, mask, tgt)

    def gpipe_loss(p):
        mlm, nsp, aux = pb.apply({"params": p}, ids, mask)
        return _pretrain_loss(mlm, nsp, tgt) + W * aux

    want_l, want_g = jax.jit(jax.value_and_grad(gpipe_loss))(
        variables["params"])
    np.testing.assert_allclose(float(loss), float(want_l), rtol=1e-5)
    for name in ("embed", "stages", "heads"):
        for a, b in zip(jax.tree.leaves(grads[name]),
                        jax.tree.leaves(want_g[name])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=1e-5)
    # the router grads specifically must be nonzero (the aux term is
    # the only thing training the router toward balance)
    router = [a for path, a in jax.tree_util.tree_leaves_with_path(
        grads["stages"]) if "router" in str(path)]
    assert router and all(float(jnp.abs(r).max()) > 0 for r in router)


@pytest.mark.parametrize("dispatch", ["dense", "capacity"])
def test_bert_1f1b_tp_moe_matches_gpipe_autodiff(dispatch):
    """dp x tp x pp with MoE stages on the interleaved schedule — the
    composition round 4 fenced off ("aux-leaf out_specs don't compose
    with partial-manual tp"). Re-probed round 5: it compiles and the
    full grad tree — embed, stages (incl. EARLY-stage router grads
    credited through the aux leaf's cotangent chain), heads — pins
    exactly against autodiff through the GPipe apply path, for both
    dispatch modes, so the fence is lifted and this test keeps it
    lifted."""
    from apex_tpu import models

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                ("data", "model", "pipe"))
    cfg = models.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, moe_experts=4,
        moe_dispatch=dispatch)
    pb = models.PipelinedBert(cfg, mesh, pp=2, num_microbatches=2,
                              batch_axis="data", tp_axis="model")
    ids, mask, tgt = _bert_batch()
    variables = pb.shard_variables(pb.init(jax.random.PRNGKey(1), ids,
                                           mask))
    W = 0.01
    with mesh:
        loss, grads = jax.jit(
            lambda v, i, m, t: pb.loss_and_grad_1f1b(
                v, i, _pretrain_loss, t, attention_mask=m,
                moe_aux_weight=W))(variables, ids, mask, tgt)

        def gpipe_loss(p):
            mlm, nsp, aux = pb.apply({"params": p}, ids, mask)
            return _pretrain_loss(mlm, nsp, tgt) + W * aux

        want_l, want_g = jax.jit(jax.value_and_grad(gpipe_loss))(
            variables["params"])
    np.testing.assert_allclose(float(loss), float(want_l), rtol=1e-5)
    for name in ("embed", "stages", "heads"):
        for a, b in zip(jax.tree.leaves(grads[name]),
                        jax.tree.leaves(want_g[name])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=1e-5)
    router = [a for path, a in jax.tree_util.tree_leaves_with_path(
        grads["stages"]) if "router" in str(path)]
    assert router and all(float(jnp.abs(r).max()) > 0 for r in router)


def test_bert_1f1b_ulysses_dp_sp_pp_matches_monolithic():
    """dp x sp x pp on the interleaved schedule with Ulysses attention
    (all_to_all + local attention — scan-free, so its collectives are
    sound inside the schedule's branches): loss, embed, stage, and head
    grads match the monolithic full-attention autodiff."""
    from apex_tpu import models, parallel

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                ("data", "sp", "pipe"))
    cfg = models.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    uly = parallel.make_ulysses_attention("sp")
    pb = models.PipelinedBert(cfg, mesh, pp=2, num_microbatches=2,
                              batch_axis="data", seq_axis="sp",
                              attention_fn=uly)
    ids, mask, tgt = _bert_batch()
    variables = pb.init(jax.random.PRNGKey(1), ids, mask)
    loss, grads = jax.jit(
        lambda v, i, m, t: pb.loss_and_grad_1f1b(
            v, i, _pretrain_loss, t, attention_mask=m))(
        variables, ids, mask, tgt)

    seq_params = _monolithic_params(variables, 2, 1)

    def mono_loss(p):
        mlm, nsp = models.BertForPreTraining(cfg).apply(
            {"params": p}, ids, mask, deterministic=True)
        return _pretrain_loss(mlm, nsp, tgt)

    want_l, want_g = jax.value_and_grad(mono_loss)(seq_params)
    np.testing.assert_allclose(float(loss), float(want_l), rtol=1e-5)
    for k in grads["heads"]:
        for a, b in zip(jax.tree.leaves(grads["heads"][k]),
                        jax.tree.leaves(want_g[k])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=2e-5)
    for k in grads["embed"]:
        for a, b in zip(jax.tree.leaves(grads["embed"][k]),
                        jax.tree.leaves(want_g["encoder"][k])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=2e-5)
    for li in range(cfg.num_hidden_layers):
        got_li = jax.tree.map(lambda a: a[li],
                              grads["stages"]["layer_0"])
        for a, b in zip(jax.tree.leaves(got_li),
                        jax.tree.leaves(want_g["encoder"][f"layer_{li}"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=2e-5)


def test_bert_1f1b_ring_rejected():
    """The ring attention factory is tagged onef1b_compatible=False;
    the 1F1B path must refuse it with an actionable message instead of
    silently miscomputing."""
    from apex_tpu import models, parallel

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                ("data", "sp", "pipe"))
    cfg = _bert_cfg()
    ring = parallel.make_ring_attention("sp")
    pb = models.PipelinedBert(cfg, mesh, pp=2, num_microbatches=2,
                              batch_axis="data", seq_axis="sp",
                              attention_fn=ring)
    ids, mask, tgt = _bert_batch()
    variables = pb.init(jax.random.PRNGKey(1), ids, mask)
    with pytest.raises(NotImplementedError, match="ring"):
        pb.loss_and_grad_1f1b(variables, ids, _pretrain_loss, tgt,
                              attention_mask=mask)


def test_bert_1f1b_dp_tp_pp_matches_monolithic():
    """dp x tp x pp on the INTERLEAVED schedule (round 4): Megatron
    tensor parallelism inside 1F1B via the same partial-manual
    shard_map as the GPipe path. Sound because GSPMD's TP collectives
    are plain (not scan-carried) and every model-axis group member
    takes the same cond branch per tick — the proven-safe class from
    the ring root-cause bisection (tools/repro_ring_1f1b.py). Loss,
    stage, embed and head grads pinned against the monolithic model.
    fp32, matching the GPipe dp x tp x pp tier (bf16 inside
    partial-manual crashes this build's XLA CPU backend)."""
    from apex_tpu import models

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                ("data", "model", "pipe"))
    cfg = models.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    pb = models.PipelinedBert(cfg, mesh, pp=2, num_microbatches=2,
                              batch_axis="data", tp_axis="model")
    ids, mask, tgt = _bert_batch()
    raw = pb.init(jax.random.PRNGKey(1), ids, mask)
    variables = pb.shard_variables(raw)
    with mesh:
        loss, grads = jax.jit(
            lambda v, i, m, t: pb.loss_and_grad_1f1b(
                v, i, _pretrain_loss, t, attention_mask=m))(
            variables, ids, mask, tgt)

    seq_params = _monolithic_params(raw, 2, 1)

    def mono_loss(p):
        mlm, nsp = models.BertForPreTraining(cfg).apply(
            {"params": p}, ids, mask, deterministic=True)
        return _pretrain_loss(mlm, nsp, tgt)

    want_l, want_g = jax.value_and_grad(mono_loss)(seq_params)
    np.testing.assert_allclose(float(loss), float(want_l), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads["heads"]),
                    jax.tree.leaves({k: want_g[k]
                                     for k in grads["heads"]})):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)
    for k in grads["embed"]:
        for a, b in zip(jax.tree.leaves(grads["embed"][k]),
                        jax.tree.leaves(want_g["encoder"][k])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)
    for li in range(cfg.num_hidden_layers):
        got_li = jax.tree.map(lambda a: a[li],
                              grads["stages"]["layer_0"])
        for a, b in zip(jax.tree.leaves(got_li),
                        jax.tree.leaves(want_g["encoder"][f"layer_{li}"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)
    # the TP placement survived into the stage grads
    qk_g = grads["stages"]["layer_0"]["attention"]["query"]["kernel"]
    assert "model" in set(
        a for e in qk_g.sharding.spec if e is not None
        for a in (e if isinstance(e, tuple) else (e,))), qk_g.sharding.spec
