"""The plain references against the program at a tiny size on the CPU:
the comparisons the chip makes, rehearsed.  Logits against
``GPTLMHeadModel``; loss and updated parameters after one step against
``chip_smoke.build_trainer``; served tokens (prefill, then decode and
verify through the paged cache) against ``InferenceServer``; and the
lower-precision controls, which have to come out as not correct."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_root  # noqa: E402
from benchmarks.harness import compare, program, serve, weights  # noqa: E402
from benchmarks.harness import train as train_runner  # noqa: E402
from benchmarks.harness.spec import load_cell  # noqa: E402

SIZES = tiny_root.TINY_SIZES


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root.make(tmp_path_factory.mktemp("bench"))[0]


@pytest.fixture(scope="module")
def ref(root):
    return load_cell("tiny-train", root).reference()


def params_for(ref, seed, dtype=jnp.float32):
    return weights.make_params(ref.param_table(SIZES), seed, dtype,
                               ref.weight_std(SIZES))


def test_weights_have_the_programs_tree_and_follow_the_seed(root, ref):
    _, models, _, _ = program.import_program()
    model = models.GPTLMHeadModel(
        load_cell("tiny-train", root).model_config(models))
    theirs = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"])
    big = 2 ** 31 + 7
    mine = params_for(ref, big)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert jax.tree.map(lambda a: a.shape, mine) \
        == jax.tree.map(lambda a: a.shape, theirs)
    again, other = params_for(ref, big), params_for(ref, 7)
    w = lambda p: np.asarray(p["block_1"]["mlp_in"]["kernel"])
    assert np.array_equal(w(mine), w(again)) and not np.array_equal(
        w(mine), w(other))
    assert abs(float(w(mine).std()) - 0.02) < 0.002
    assert not np.array_equal(w(mine), np.asarray(
        mine["block_0"]["mlp_in"]["kernel"]))       # layers differ
    assert float(mine["final_ln"]["scale"].min()) == 1.0
    assert float(jnp.abs(mine["block_0"]["mlp_in"]["bias"]).max()) == 0.0


def test_logits_match_the_programs_model(root, ref):
    _, models, _, _ = program.import_program()
    model = models.GPTLMHeadModel(
        load_cell("tiny-train", root).model_config(models))
    params = params_for(ref, 3)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, (3, 96)),
                      jnp.int32)
    with jax.default_matmul_precision("highest"):
        theirs = model.apply({"params": params}, ids)
    mine = ref.logits(ref.stacked(params, SIZES), ids, SIZES)
    assert mine.shape == theirs.shape == (3, 96, 512)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs),
                               atol=2e-5)
    # and the loss is the program's loss
    np.testing.assert_allclose(
        float(ref.next_token_loss(ref.stacked(params, SIZES), ids, SIZES)),
        float(models.lm_loss(theirs, ids)), rtol=1e-5)


def test_one_step_of_the_trainer_loss_and_updated_parameters(root, ref):
    cell = load_cell("tiny-train", root)
    trainer = train_runner.Trainer(cell, jax.devices()[:1])
    feed = trainer.feed(5)
    ids = jnp.asarray(next(feed))
    p0 = trainer.fresh_params(5, trainer.repl)
    params, opt_state, loss = trainer.step(
        trainer.fresh_params(5, trainer.repl),
        trainer.optimizer.init(p0), ids)
    losses, grad, p1 = ref.train_steps(
        ref.stacked(p0, SIZES), [ids], SIZES, trainer.hyper)
    assert float(loss) == pytest.approx(float(losses[0]), rel=1e-4)
    moved = ref.unstacked_leaf_norms(
        jax.tree.map(jnp.subtract, p1, ref.stacked(p0, SIZES)), SIZES)
    mine = ref.unstacked_leaf_norms(
        jax.tree.map(jnp.subtract, ref.stacked(params, SIZES),
                     ref.stacked(p0, SIZES)), SIZES)
    grads = ref.unstacked_leaf_norms(grad, SIZES)
    keep = compare.moving_leaves(grads)
    gap, worst, _ = compare.norm_gaps(mine, moved, keep)
    assert gap < 0.05, worst
    # the first Adam step moves every element by about lr: the same
    # direction, not only the same length
    a = np.asarray(params["wte"]["embedding"] - p0["wte"]["embedding"])
    b = np.asarray(p1["wte"]["embedding"] - p0["wte"]["embedding"])
    assert (a * b).sum() / np.linalg.norm(a) / np.linalg.norm(b) > 0.9


@pytest.mark.parametrize("precision,correct", [("bfloat16", True),
                                               ("fp8", False)])
def test_training_control_fails_where_the_next_lower_precision_is_used(
        root, precision, correct):
    """The reference put in the program's place: in the precision the
    configuration states it passes, in the next lower one it does not."""
    cell = load_cell("tiny-train", root)
    trainer = train_runner.Trainer(cell, jax.devices()[:1])
    feed = trainer.feed(9)
    batches = [next(feed) for _ in range(train_runner.CHECK_STEPS)]
    theirs = trainer.reference(9, batches)
    low = trainer.reference(9, batches, precision)
    compared, _ = compare.compare_training(low, theirs,
                                           SIZES["limits"]["train"])
    assert compare.verdict(compared) is correct, compared


@pytest.mark.parametrize("fault,keep", [("half_batch", 1)])
def test_half_the_batch_left_out_reads_far_from_the_reference(root, fault,
                                                              keep):
    cell = load_cell("tiny-train", root)
    trainer = train_runner.Trainer(cell, jax.devices()[:1])
    feed = trainer.feed(9)
    batches = [next(feed) for _ in range(train_runner.CHECK_STEPS)]
    compared, _ = compare.compare_training(
        trainer.reference(9, batches, keep_rows=keep),
        trainer.reference(9, batches), SIZES["limits"]["train"])
    assert not compare.verdict(compared)
    assert compared["grad_norm_gap"]["value"] > 0.25


def test_served_tokens_are_the_references_best_and_fp8s_are_not(root, ref):
    """Prefill in chunks, then decode and verify through the paged cache,
    greedy: every emitted token has to be the plain forward pass's best
    (float32 here, so to rounding).  The control reads far off."""
    _, models, serving, _ = program.import_program()
    cell = load_cell("tiny-backlog", root)
    params = params_for(ref, 4)
    server = serve.build_server(cell, models, serving, params)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, n).tolist()
               for n in (5, 40, 100, 17, 64)]
    prompts.append(rng.integers(0, 512, 4).tolist() * 8)   # drafts hit
    reqs = server.generate(prompts, 24, return_requests=True)
    # a draft never covers a last token: this one needs the plain decode
    reqs += server.generate([prompts[1]], 2, return_requests=True)
    st = server.stats()
    server.close()
    families = {k.split("[")[0] for k in st["programs"]["by_program"]}
    assert {"chunk_prefill_sampled", "decode_sampled",
            "verify_sampled"} <= families
    rows = [(list(r.prompt), list(r.generated)) for r in reqs]
    gaps = serve.served_gaps(ref, params, rows, SIZES, 2)
    assert gaps.size == 6 * 24 + 2 and float(gaps.max()) < 1e-3
    low = serve.served_gaps(ref, params, rows, SIZES, 2, tokens_of="fp8")
    assert float(low.max()) > SIZES["limits"]["serve"]["served_gap_max"]
    compared, _ = compare.compare_served(low, SIZES["limits"]["serve"])
    assert not compare.verdict(compared)


def test_sampled_tokens_lie_in_the_references_nucleus(root, ref):
    """Sampled at temperature 0.8 through chunked prefill and decode:
    the reference ranks less than ``top_p`` of its mass above every
    served token.  A server that leaves the nucleus out (``top_p`` 1)
    serves tokens far outside it, and with a limit that is not
    correct."""
    _, models, serving, _ = program.import_program()
    from apex_tpu.serving.scheduler import SamplingParams
    cell = load_cell("tiny-chat", root)
    params = params_for(ref, 4)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 512, n).tolist() for n in (5, 40, 100, 17)]
    over = {}
    for top_p in (0.5, 1.0):
        server = serve.build_server(cell, models, serving, params)
        reqs = server.generate(
            prompts, 24, return_requests=True, sampling=[
                SamplingParams(temperature=0.8, top_p=top_p, seed=i)
                for i in range(len(prompts))])
        server.close()
        rows = [(list(r.prompt), list(r.generated)) for r in reqs]
        mass = serve.served_mass_above(ref, params, rows, SIZES, 2, 0.8)
        assert mass.size == 4 * 24 and 0.0 <= mass.min()
        limits = dict(SIZES["limits"]["serve"], sampled_over_top_p=0.01)
        compared, notes = compare.compare_served(
            np.zeros(3), limits, mass, 0.5)
        over[top_p] = compared["sampled_over_top_p"]["value"]
        assert notes["sampled_tokens_read"] == mass.size
        assert compare.verdict(compared) == (top_p == 0.5)
    assert over[0.5] < 1e-4 and over[1.0] > 0.3
    # with no limit in the configuration the number is printed, not compared
    compared, notes = compare.compare_served(
        np.zeros(3), SIZES["limits"]["serve"], mass, 0.5)
    assert set(compared) == {"served_gap_max"}
    assert notes["not_compared"] == {"sampled_over_top_p": over[1.0]}


@pytest.mark.parametrize("cell,kind,limits", [
    ("gpt2m-train-1chip", "train", {"grad_norm_gap": 0.02}),
    # read at 32 rows, the fp8 control's worst gradient leaf separates
    # from the program's: this mix holds it under that reading
    ("gpt2m-train-ddp4", "train", {"grad_norm_gap": 0.008}),
    ("gpt2xl-chat-open", "serve", {"served_gap_max": 0.15}),
    ("gpt2xl-doc-backlog", "serve", {"served_gap_max": 0.15}),
])
def test_a_cell_is_held_to_its_mixs_own_limits_where_it_has_them(
        cell, kind, limits):
    c = load_cell(cell)
    got = compare.limits_for(c, kind)
    assert {k: got[k] for k in limits} == limits
    if kind == "train":
        assert got["delta_norm_gap"] == 0.02
        assert got["delta_norm_gap_median"] == 0.0005
    assert (got is c.config["limits"][kind]) == (
        c.traffic_name not in c.config["limits"])
