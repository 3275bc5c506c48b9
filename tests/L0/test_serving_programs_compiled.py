"""The engine's serving programs compiled at GPT-2 XL's size for a
described v5e by the real Mosaic and XLA:TPU compilers, with no chip:
what the pool's layout and "attend through the table" promise is read
off the compiled text (``docs/serving.md``, "The pool's layout").

- nothing but the donated pool and its in-place updates is as large as
  the pool: no relayout copy, no transpose;
- decode and verify build nothing of ``slots x max_context`` K/V size;
- decode holds the ``_decode_kernel`` Mosaic call, verify the
  ``_verify_kernel`` one, chunk prefill the ``_chunk_kernel`` one;
- the decode program's temporaries stay under 2 GiB at 8 slots, and it
  compiles within the chip's 16 GiB at 32.

All in this one file, inside fixtures, as the ``on-chip-measurement``
guide prescribes: only the worker that runs this file loads libtpu.
The CPU backend's decode program is held to the same reading of its
text at a small size."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from apex_tpu import models
from apex_tpu.serving.engine import DecodeEngine

os.environ.setdefault("TPU_LOG_DIR", "disabled")

pytestmark = pytest.mark.serving

GIB = 2 ** 30
XL = dict(vocab_size=50257, hidden_size=1600, num_hidden_layers=48,
          num_attention_heads=25, intermediate_size=6400,
          max_position_embeddings=1024, hidden_dropout_prob=0.0,
          attention_probs_dropout_prob=0.0)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_tpu(monkeypatch):
    """The kernels' gates and the engine's choice of attention path ask
    ``on_tpu()``; answer as the chip would."""
    from apex_tpu.ops import pallas_utils
    import apex_tpu.normalization.fused_layer_norm  # noqa: F401
    import apex_tpu.ops.decode_attention  # noqa: F401
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    import apex_tpu.ops.grouped_matmul  # noqa: F401
    import apex_tpu.ops.short_conv  # noqa: F401
    for mod in ("apex_tpu.ops.decode_attention",
                "apex_tpu.ops.grouped_matmul",
                "apex_tpu.ops.short_conv",
                "apex_tpu.normalization.fused_layer_norm",
                "apex_tpu.serving.engine"):
        monkeypatch.setattr(sys.modules[mod], "on_tpu", lambda: True)
    # a compile for a described chip cannot be read back from the cache
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \(?(\w+)\[([\d,]*)\]\S* ([\w-]+)\(")


def instructions(text):
    """``(name, element count, opcode)`` of every instruction of a
    compiled module's text whose result is one array."""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            dims = [int(d) for d in m.group(3).split(",") if d]
            yield m.group(1), int(np.prod(dims)), m.group(4)


# what may be as large as the pool: the donated parameter, a view of
# it, and an update of it in place (alone or as a fusion's root)
IN_PLACE = {"parameter", "bitcast", "get-tuple-element", "scatter",
            "dynamic-update-slice", "fusion", "while", "conditional",
            "call"}


def pool_sized_strays(text, pool_elements):
    return [(name, op) for name, n, op in instructions(text)
            if n == pool_elements and op not in IN_PLACE]


def context_sized(text, engine):
    """Instructions holding every slot's ``max_context`` keys or
    values (or both, packed), one layer's or all layers'."""
    cfg = engine.cache_cfg
    one = (engine.max_batch_size * engine.blocks_per_seq
           * engine.block_size * cfg.num_heads * cfg.head_dim)
    sizes = {one * f * l for f in (1, 2) for l in (1, cfg.num_layers)}
    return [(name, op) for name, n, op in instructions(text)
            if n in sizes]


def _shapes(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def _xl_engine(slots, sharding, monkeypatch):
    """A GPT-2 XL engine that holds shapes only: the parameters and the
    pool are described, never allocated."""
    cfg = models.GPTConfig(**XL)
    model = models.GPTLMHeadModel(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, jnp.bfloat16, sharding=sharding), params)
    import apex_tpu.serving.engine as engine_mod
    real = engine_mod.init_kv_cache
    monkeypatch.setattr(
        engine_mod, "init_kv_cache",
        lambda cfg, **kw: jax.eval_shape(lambda: real(cfg)))
    engine = DecodeEngine(cfg, params, max_batch_size=slots,
                          max_context=1024)
    engine.cache = _shapes(engine.cache, sharding)
    return engine


def _ints(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _compile(jit_fn, engine, *args):
    """The logits twins: they donate the pool on every backend (the
    sampled twins do not where the process runs on the CPU)."""
    return jit_fn.lower(engine.params, engine.cache, *args).compile()


def test_xl_decode_in_place_at_8_slots(one_chip, as_on_tpu, monkeypatch):
    e = _xl_engine(8, one_chip, monkeypatch)
    assert e.attention_paths["decode"] == "table"
    b, nb = 8, e.blocks_per_seq
    exe = _compile(e._decode_jit, e, _ints(one_chip, b),
                   _ints(one_chip, b), _ints(one_chip, b, nb))
    text = exe.as_text()
    pool = int(np.prod(e.cache["kv"].shape))
    assert not pool_sized_strays(text, pool)
    assert not context_sized(text, e)
    assert "_decode_kernel" in text
    assert exe.memory_analysis().temp_size_in_bytes < 2 * GIB


def test_xl_verify_in_place_at_8_slots(one_chip, as_on_tpu, monkeypatch):
    e = _xl_engine(8, one_chip, monkeypatch)
    assert e.attention_paths["verify"] == "table"
    b, nb = 8, e.blocks_per_seq
    exe = _compile(e._verify_jit, e, _ints(one_chip, b, 5),
                   _ints(one_chip, b), _ints(one_chip, b),
                   _ints(one_chip, b, nb))
    text = exe.as_text()
    assert not pool_sized_strays(text, int(np.prod(e.cache["kv"].shape)))
    assert not context_sized(text, e)
    assert "_verify_kernel" in text and "_decode_kernel" not in text
    assert exe.memory_analysis().temp_size_in_bytes < 2 * GIB


def test_xl_chunk_in_place_at_8_slots(one_chip, as_on_tpu, monkeypatch):
    e = _xl_engine(8, one_chip, monkeypatch)
    assert e.attention_paths["chunk_prefill"] == "table"
    nb = e.blocks_per_seq
    exe = _compile(e._chunk_jit, e, _ints(one_chip, 1, 256),
                   _ints(one_chip, 1), _ints(one_chip, 1),
                   _ints(one_chip, 1, nb))
    text = exe.as_text()
    assert not pool_sized_strays(text, int(np.prod(e.cache["kv"].shape)))
    assert not context_sized(text, e)
    assert "_chunk_kernel" in text
    assert exe.memory_analysis().temp_size_in_bytes < 2 * GIB


def test_xl_decode_fits_the_chip_at_32_slots(one_chip, as_on_tpu, monkeypatch):
    e = _xl_engine(32, one_chip, monkeypatch)
    b, nb = 32, e.blocks_per_seq
    exe = _compile(e._decode_jit, e, _ints(one_chip, b),
                   _ints(one_chip, b), _ints(one_chip, b, nb))
    m = exe.memory_analysis()
    assert not pool_sized_strays(exe.as_text(),
                                 int(np.prod(e.cache["kv"].shape)))
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert total < 16 * GIB
    assert m.temp_size_in_bytes < 2 * GIB


def test_cpu_decode_writes_in_place_at_a_small_size():
    """The gathered path the CPU runs: nothing pool-sized but the pool
    and its updates, and no all-slot context either (each layer gathers
    its own)."""
    cfg = models.GPTConfig(vocab_size=97, hidden_size=64,
                           num_hidden_layers=3, num_attention_heads=2,
                           intermediate_size=128,
                           max_position_embeddings=128,
                           hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0)
    params = models.GPTLMHeadModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    e = DecodeEngine(cfg, params, max_batch_size=4, max_context=128,
                     num_blocks=64, cache_dtype=jnp.float32)
    assert e.attention_paths == {"decode": "gathered",
                                 "verify": "gathered",
                                 "chunk_prefill": "gathered"}
    text = e.decode_hlo()
    pool = int(np.prod(e.cache["kv"].shape))
    assert not [s for s in pool_sized_strays(text, pool)
                if s[1] != "copy"], "a transpose of the pool"
    # all layers' contexts at once, as the old program gathered them
    every_layer = (cfg.num_hidden_layers * 4 * 128 * 2 * 32)
    assert not [n for n, c, _ in instructions(text)
                if c in (every_layer, 2 * every_layer)]
    assert e.memory_info()["decode_temp_bytes"] is not None


# -- a model with window layers: the pool by kind of layer ---------------------

KEXAONE = dict(vocab_size=19200, num_hidden_layers=8, num_experts=128,
               experts_held=(0, 8))


def _shape_engine(cfg, slots, max_context, sharding, monkeypatch):
    """An engine of ``cfg`` that holds shapes only: the parameters and
    the pool are described, never allocated."""
    model = cfg.build_model()
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, jnp.bfloat16, sharding=sharding), params)
    import apex_tpu.serving.engine as engine_mod
    real = engine_mod.init_kv_cache
    monkeypatch.setattr(
        engine_mod, "init_kv_cache",
        lambda cfg, **kw: jax.eval_shape(lambda: real(cfg)))
    monkeypatch.setattr(
        engine_mod.DecodeEngine, "_fresh_cache",
        lambda self, fresh=engine_mod.DecodeEngine._fresh_cache:
        jax.eval_shape(lambda: fresh(self)))
    engine = DecodeEngine(cfg, params, max_batch_size=slots,
                          max_context=max_context)
    engine.cache = _shapes(engine.cache, sharding)
    return engine


def kernels_launched(text):
    """How often each Pallas kernel's call stands in a compiled module's
    text, by the kernel's name."""
    out = {}
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%(_\w*kernel)(?:\.\d+)? = ", line)
        if m and "custom-call" in line:
            out[m.group(1)] = out.get(m.group(1), 0) + 1
    return out


@pytest.mark.parametrize("program", ["decode", "verify", "chunk"])
def test_kexaone_programs_at_the_cells_size(one_chip, as_on_tpu, monkeypatch,
                                            program):
    """``k-exaone-236b-a23b`` as its cell runs it: 16 slots of 32,768,
    two full layers through the table and six window layers through a
    ring of 512 rows a slot.  Each kind's kernel is in the text under
    its own name, nothing pool-sized is copied, and weights, both pools
    and temporaries fit the chip."""
    cfg = models.ExaoneMoeConfig(**KEXAONE)
    e = _shape_engine(cfg, 16, 32768, one_chip, monkeypatch)
    assert set(e.attention_paths.values()) == {"table"}
    assert e.ring_rows == 512 and e.max_fed_rows == 384
    assert e.cache["kv"].shape == (2, (16 * 2048 + 1) * 16, 2048)
    assert e.cache["kv_window"].shape == (6, (16 * 32 + 1) * 16, 2048)
    b, nb = 16, e.blocks_per_seq
    if program == "decode":
        exe = _compile(e._decode_jit, e, _ints(one_chip, b),
                       _ints(one_chip, b), _ints(one_chip, b, nb))
        names = {"_decode_kernel": 2, "_window_decode_kernel": 6}
    elif program == "verify":
        exe = _compile(e._verify_jit, e, _ints(one_chip, b, 5),
                       _ints(one_chip, b), _ints(one_chip, b),
                       _ints(one_chip, b, nb))
        names = {"_verify_kernel": 2, "_window_verify_kernel": 6}
    else:
        exe = e._chunk_jit.lower(
            e.params, e.cache, _ints(one_chip, 1, 256), _ints(one_chip, 1),
            _ints(one_chip, 1), _ints(one_chip, 1, nb),
            slot=_ints(one_chip, 1)).compile()
        names = {"_chunk_kernel": 2, "_window_chunk_kernel": 6}
    text = exe.as_text()
    launched = kernels_launched(text)
    assert {k: v for k, v in launched.items() if "moe" not in k} == names
    assert launched["_moe_gmm_kernel"] == 7 * 3
    for leaf in ("kv", "kv_window"):
        assert not pool_sized_strays(text, int(np.prod(e.cache[leaf].shape)))
    m = exe.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(program, "arguments", m.argument_size_in_bytes / GIB, "temp",
          m.temp_size_in_bytes / GIB, "total", total / GIB)
    assert total < 15 * GIB
    assert m.temp_size_in_bytes < 1.5 * GIB


# -- a model whose conv layers keep a state: a ring of a few rows a slot ----------

LFM2 = dict(num_hidden_layers=9, num_dense_layers=1,
            layer_types=("conv", "full_attention", "conv", "conv", "conv",
                         "full_attention", "conv", "conv", "conv"))


@pytest.mark.parametrize("program", ["decode", "verify", "chunk"])
def test_lfm2_programs_at_the_cells_size(one_chip, as_on_tpu, monkeypatch,
                                         program):
    """``lfm2-24b-a2b`` as its cell runs it: 64 slots of 6,144, two
    attention layers through the table and seven conv layers through a
    ring of 16 rows a slot.  The attention kernel stands once for each
    attention layer, ``_short_conv_kernel`` once for each conv layer, the
    grouped product three times for each expert layer; nothing
    pool-sized is copied; weights, pool, state and temporaries fit the
    chip."""
    cfg = models.Lfm2MoeConfig(**LFM2)
    e = _shape_engine(cfg, 64, 6144, one_chip, monkeypatch)
    assert set(e.attention_paths.values()) == {"table"}
    assert e.state_rows == 16 and e.max_verify_rows == 14
    assert e.cache["kv"].shape == (2, (64 * 384 + 1) * 16, 1024)
    assert e.cache["conv_state"].shape == (7, 64, 16, 2048)
    b, nb = 64, e.blocks_per_seq
    if program == "decode":
        exe = _compile(e._decode_jit, e, _ints(one_chip, b),
                       _ints(one_chip, b), _ints(one_chip, b, nb))
        attention = "_decode_kernel"
    elif program == "verify":
        exe = _compile(e._verify_jit, e, _ints(one_chip, b, 5),
                       _ints(one_chip, b), _ints(one_chip, b),
                       _ints(one_chip, b, nb))
        attention = "_verify_kernel"
    else:
        exe = e._chunk_jit.lower(
            e.params, e.cache, _ints(one_chip, 1, 256), _ints(one_chip, 1),
            _ints(one_chip, 1), _ints(one_chip, 1, nb),
            slot=_ints(one_chip, 1)).compile()
        attention = "_chunk_kernel"
    text = exe.as_text()
    assert kernels_launched(text) == {attention: 2, "_short_conv_kernel": 7,
                                      "_moe_gmm_kernel": 8 * 3}
    for leaf in ("kv", "conv_state"):
        assert not pool_sized_strays(text, int(np.prod(e.cache[leaf].shape)))
    m = exe.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(program, "arguments", m.argument_size_in_bytes / GIB, "temp",
          m.temp_size_in_bytes / GIB, "total", total / GIB)
    assert total < 15 * GIB
    assert m.temp_size_in_bytes < 1.5 * GIB


@pytest.mark.parametrize("family", ["gpt2", "deepseek_v3"])
def test_the_families_that_keep_every_token_launch_what_they_did(
        one_chip, as_on_tpu, monkeypatch, family):
    """The programs of a model without window layers are the ones they
    were before the pool had kinds: one leaf, the kernels of one kind
    under their names, once a layer, and the chunk program takes no slot
    (an argument it has no use for is not in its compiled text)."""
    if family == "gpt2":
        cfg = models.GPTConfig(**XL)
        kernels, layers = ("_decode_kernel", "_verify_kernel",
                           "_chunk_kernel"), 48
        context = 1024
    else:
        cfg = models.DeepseekV3Config(num_hidden_layers=3)
        kernels, layers = ("_latent_decode_kernel", "_latent_verify_kernel",
                           "_latent_chunk_kernel"), 3
        context = 4096
    e = _shape_engine(cfg, 8, context, one_chip, monkeypatch)
    assert e.layers is None and e.window_cfg is None \
        and e.max_fed_rows is None
    assert set(e.cache) - {"routed"} == {"kv"}
    b, nb = 8, e.blocks_per_seq
    texts = [
        _compile(e._decode_jit, e, _ints(one_chip, b), _ints(one_chip, b),
                 _ints(one_chip, b, nb)).as_text(),
        _compile(e._verify_jit, e, _ints(one_chip, b, 5),
                 _ints(one_chip, b), _ints(one_chip, b),
                 _ints(one_chip, b, nb)).as_text(),
        _compile(e._chunk_jit, e, _ints(one_chip, 1, 256),
                 _ints(one_chip, 1), _ints(one_chip, 1),
                 _ints(one_chip, 1, nb)).as_text()]
    for text, kernel in zip(texts, kernels):
        launched = {k: v for k, v in kernels_launched(text).items()
                    if "moe" not in k and "layer_norm" not in k
                    and "ln_" not in k}
        assert launched == {kernel: layers}, launched
    args, kw = e._chunk_args([1, 2, 3], 0, [1], 256, slot=5)
    assert kw == {} and len(args) == 4


# -- the stochastic twins select their thresholds: no sort in the text ---------

_TINY = {
    "gpt2": lambda: models.GPTConfig(
        vocab_size=97, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=128,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0),
    "deepseek_v3": lambda: models.DeepseekV3Config(
        vocab_size=509, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1,
        num_experts_per_tok=2, first_k_dense_replace=1, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
        max_position_embeddings=256),
}


_SORTED = re.compile(
    r'"stablehlo\.sort"\(.*?\}\) : \(([^)]*)\)|chlo\.top_k\([^)]*\) : (\S+)',
    re.DOTALL)


def _sorted_rows(text):
    """The last dimension of every operand that a lowered module's text
    sorts or takes a ``top_k`` of."""
    return [int(dims.split("x")[-2])
            for m in _SORTED.finditer(text)
            for dims in re.findall(r"tensor<([^>]*)>",
                                   m.group(1) or m.group(2))]


@pytest.mark.parametrize("family", sorted(_TINY))
def test_the_stochastic_twins_hold_no_sort(family):
    """``chunk_prefill_stoch``, ``decode_stoch`` and ``verify_stoch`` as
    they are lowered: the fused sampler finds its top-k and top-p
    thresholds by selection (``ops/sampling.py``), so nothing as long
    as the vocabulary is sorted (or short-listed by a ``top_k``) in any
    of the three; an expert family's router still takes its few of a
    few experts.  This is the record that the mechanism engages: it has
    no counter because it has no other path."""
    assert _sorted_rows(jax.jit(lambda x: -jnp.sort(-x)).lower(
        jnp.zeros((4, 97))).as_text()) == [97], "the reading finds a sort"
    cfg = _TINY[family]()
    params = cfg.build_model().init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    e = DecodeEngine(cfg, params, max_batch_size=4, max_context=128,
                     cache_dtype=jnp.float32)
    b, nb = 4, e.blocks_per_seq

    def sampling(n):
        return (np.full((n,), 0.8, np.float32), np.zeros((n,), np.int32),
                np.full((n,), 0.95, np.float32), np.arange(n, dtype=np.int32))

    zeros, tables = np.zeros((b,), np.int32), np.zeros((b, nb), np.int32)
    chunk_args, chunk_kw = e._chunk_args([1, 2, 3], 0, [1], 16,
                                         sampling=sampling(1))
    programs = {
        "chunk_prefill_stoch": (e._chunk_stoch_jit, chunk_args, chunk_kw),
        "decode_stoch": (e._decode_stoch_jit, e._decode_args(
            zeros, zeros, tables, sampling=sampling(b)), {}),
        "verify_stoch": (e._verify_stoch_jit, e._verify_args(
            np.zeros((b, 5), np.int32), zeros, zeros, tables,
            sampling=sampling(b)), {}),
    }
    for name, (jit_fn, args, kw) in programs.items():
        text = jit_fn.lower(e.params, e.cache, *args, **kw).as_text()
        assert "stablehlo.while" in text, name    # the 32 turns, one loop
        assert cfg.vocab_size not in _sorted_rows(text), name
        assert _sorted_rows(text) or family == "gpt2", name
