"""The vocabulary of device scopes on the compiled programs.

For each family's tiny configuration the serving engine's decode,
verify and chunk programs, in their greedy (``_sampled``) and
stochastic (``_stoch``) twins, and the amp training step of
``chip_smoke.build_trainer`` are compiled on the CPU, and the optimized
HLO's ``metadata={op_name=...}`` is read: every instruction that the
model, the sampler, the loss or the optimizer made lies under a name of
``DEVICE_SCOPES``, and each family's programs hold the blocks that
family has.  ``program_texts`` and ``trainer_text`` import nothing of
the vocabulary, so the same programs can be built from a checkout that
has none and compared with these."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.serving.engine import DecodeEngine
from apex_tpu.serving.scheduler import SamplingParams, Scheduler

BS, CHUNK, VERIFY = 4, 16, 3
FAMILIES = {
    "gpt": models.GPTConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0),
    "deepseek": models.DeepseekV3Config(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=96,
        moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1,
        num_experts_per_tok=2, first_k_dense_replace=1, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
        max_position_embeddings=64),
    "exaone": models.ExaoneMoeConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=96, moe_intermediate_size=32, num_experts=8,
        num_shared_experts=1, num_experts_per_tok=2,
        first_k_dense_replace=1, sliding_window=8,
        max_position_embeddings=64),
    "lfm2": models.Lfm2MoeConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        num_dense_layers=1, conv_L_cache=3,
        layer_types=("conv", "full_attention", "conv"),
        max_position_embeddings=64),
}
# the blocks each family's serving programs hold, beside those of all
EXPECTED = {
    "gpt": {"mlp"},
    "deepseek": {"mlp", "moe_router", "moe_experts", "moe_shared"},
    "exaone": {"mlp", "moe_router", "moe_experts", "moe_shared"},
    "lfm2": {"mlp", "short_conv", "moe_router", "moe_experts"},
}
EVERY_SERVING = {"embed", "norm", "attention", "kv_write", "head"}
TRAINER = {"embed", "norm", "attention", "mlp", "head", "optimizer"}
PROGRAMS = tuple(f"{p}_{twin}" for p in ("decode", "verify", "chunk")
                 for twin in ("sampled", "stoch"))


def _engine(family):
    cfg = FAMILIES[family]
    params = cfg.build_model().init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    return DecodeEngine(cfg, params, max_batch_size=2, max_context=32,
                        block_size=BS, cache_dtype=jnp.float32,
                        verify_rows=VERIFY)


def _lowered(engine, program):
    """``program`` of ``engine`` lowered at its serving shapes."""
    name, twin = program.rsplit("_", 1)
    b, nb = engine.max_batch_size, engine.blocks_per_seq
    width = 1 if name == "chunk" else b
    sampling = (Scheduler._pack_sampling(
        {0: SamplingParams(temperature=0.8, top_k=20, top_p=0.9)}, width)
        if twin == "stoch" else None)
    kw = {}
    if name == "decode":
        args = engine._decode_args(np.zeros(b, np.int32),
                                   np.zeros(b, np.int32),
                                   np.zeros((b, nb), np.int32), sampling)
    elif name == "verify":
        args = engine._verify_args(np.zeros((b, VERIFY), np.int32),
                                   np.zeros(b, np.int32),
                                   np.zeros(b, np.int32),
                                   np.zeros((b, nb), np.int32), sampling)
    else:
        args, kw = engine._chunk_args([1, 2, 3], 0, [1], CHUNK, sampling)
        name = "chunk"
    jit_fn = getattr(engine, f"_{name}_{twin}_jit")
    return jit_fn.lower(engine.params, engine.cache, *args, **kw)


def program_texts(family):
    """``{program: optimized HLO text}`` of the family's six serving
    programs."""
    engine = _engine(family)
    return {p: _lowered(engine, p).compile().as_text() for p in PROGRAMS}


def trainer_text(chips=1):
    """The optimized HLO text of the amp-O2 training step, on one device
    or in the data-parallel ``shard_map`` over ``chips`` of them."""
    import chip_smoke
    from jax.sharding import Mesh

    mesh = (None if chips == 1 else
            Mesh(np.array(jax.devices()[:chips]), ("data",)))
    cfg = FAMILIES["gpt"]
    model, optimizer, step, _ = chip_smoke.build_trainer(cfg, mesh)
    ids = jnp.ones((2 * chips, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    return step.lower(params, optimizer.init(params),
                      ids).compile().as_text()


_META = re.compile(r'op_name="([^"]*)"(?: stack_frame_id=(\d+))?')
# what the vocabulary must cover: ops the model made (under its
# module's name) and ops made by the package's own code for the model,
# the pool, the sampler, the loss or the optimizer (the innermost frame
# of the op's stack)
OWNED_SOURCES = ("apex_tpu/models/", "apex_tpu/ops/sampling.py",
                 "apex_tpu/optimizers/", "apex_tpu/ops/flatten.py",
                 "apex_tpu/amp/", "apex_tpu/serving/kv_cache.py",
                 "optax/")
MODULES = ("GPTLMHeadModel", "DeepseekV3LMHeadModel",
           "ExaoneMoeLMHeadModel", "Lfm2MoeLMHeadModel")


def _section(text, name):
    """The lines of one of the module's debug tables."""
    head = f"\n{name}\n"
    if head not in text:
        return ""
    return text.split(head, 1)[1].split("\n\n", 1)[0]


def frame_files(text):
    """``{stack frame id: the file of the frame}`` from the module's
    ``FileNames``, ``FileLocations`` and ``StackFrames`` tables."""
    files = dict(re.findall(r'^(\d+) "(.*)"$', _section(text, "FileNames")
                            .split("\nFunctionNames\n")[0], re.M))
    locs = dict(re.findall(r"^(\d+) \{file_name_id=(\d+)",
                           _section(text, "FileLocations"), re.M))
    return {frame: files[locs[loc]] for frame, loc in re.findall(
        r"^(\d+) \{file_location_id=(\d+)", _section(text, "StackFrames"),
        re.M)}


def op_metadata(text):
    """``(op_name, the file that made it)`` of every instruction with
    metadata."""
    files = frame_files(text)
    return [(name, files.get(frame, ""))
            for name, frame in _META.findall(text)]


def unscoped_owned(text):
    """The owned instructions under no device scope, with the file that
    made them.  An instruction whose op_name is no path (``add``) is the
    body of a reduction or a sort's comparison, which the operation that
    calls it carries."""
    from apex_tpu.observability import DEVICE_SCOPES
    from benchmarks.harness.blocks import block_of

    return sorted({(name, src) for name, src in op_metadata(text)
                   if "/" in name and block_of(name, DEVICE_SCOPES) is None
                   and (any(m in name.split("/") for m in MODULES)
                        or any(s in src for s in OWNED_SOURCES))})


def blocks_in(text):
    from apex_tpu.observability import DEVICE_SCOPES
    from benchmarks.harness.blocks import block_of

    return {block_of(name, DEVICE_SCOPES)
            for name, _ in op_metadata(text)} - {None}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def served(request):
    return request.param, program_texts(request.param)


@pytest.fixture(scope="module", params=[1, 4])
def trained(request):
    return request.param, trainer_text(request.param)


def test_every_owned_serving_op_lies_under_a_device_scope(served):
    family, texts = served
    for program, text in texts.items():
        loose = unscoped_owned(text)
        assert not loose, (family, program, loose[:10])


def test_each_family_holds_its_blocks(served):
    family, texts = served
    want = EVERY_SERVING | EXPECTED[family]
    for program, text in texts.items():
        got = blocks_in(text)
        assert want <= got, (family, program, sorted(want - got))
        # sampled twins all sample, on device
        assert "sample" in got, (family, program)
        assert not got & {"optimizer", "grad_exchange"}, (family, program)


def test_every_owned_training_op_lies_under_a_device_scope(trained):
    chips, text = trained
    loose = unscoped_owned(text)
    assert not loose, loose[:10]
    got = blocks_in(text)
    # the gradients' all-reduce exists only across chips
    want = TRAINER | ({"grad_exchange"} if chips > 1 else set())
    assert want <= got, sorted(want - got)
    assert not got & {"sample", "kv_write"} and not (
        chips == 1 and "grad_exchange" in got)


def test_the_old_ad_hoc_scopes_are_gone(served):
    _, texts = served
    for text in texts.values():
        for old in ("latent_attention", "window_attention",
                    "full_attention"):
            assert f"/{old}/" not in text


def test_device_scope_refuses_a_name_outside_the_vocabulary():
    from apex_tpu.observability import DEVICE_SCOPES, device_scope

    with pytest.raises(ValueError, match="no device scope"):
        device_scope("nope")
    for name in DEVICE_SCOPES:
        with device_scope(name):
            pass
    # a scope is metadata: the value computed under it is the same
    f = jax.jit(lambda x: jnp.tanh(x) * 2)

    def scoped(x):
        with device_scope("mlp"):
            return jnp.tanh(x) * 2

    x = jnp.arange(4.0)
    np.testing.assert_array_equal(f(x), jax.jit(scoped)(x))
