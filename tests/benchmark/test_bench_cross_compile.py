"""The kernels of the benchmark's cells, compiled at the cells' shapes for
a described v5e by the real Mosaic and XLA:TPU compilers, with no chip.
All in this one file, inside fixtures, as the ``on-chip-measurement``
guide prescribes: only the worker that runs this file loads libtpu."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

os.environ.setdefault("TPU_LOG_DIR", "disabled")


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
    """The kernels' gates ask ``on_tpu()``; answer as the chip would."""
    from apex_tpu.ops import pallas_utils
    import apex_tpu.normalization.fused_layer_norm  # noqa: F401
    import apex_tpu.ops.decode_attention  # noqa: F401
    import apex_tpu.ops.flash_attention  # noqa: F401
    import apex_tpu.optimizers.fused_adam  # noqa: F401
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    for mod in ("apex_tpu.ops.flash_attention",
                "apex_tpu.ops.decode_attention",
                "apex_tpu.normalization.fused_layer_norm",
                "apex_tpu.optimizers.fused_adam"):
        monkeypatch.setattr(sys.modules[mod], "on_tpu", lambda: True)
    # a compile for a described chip cannot be read back from the cache
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiled(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    return text


def _kernels(text, name):
    return text.count(f'/{name}/pallas_call') or text.count(name)


def test_flash_forward_and_backward_at_gpt2_medium(one_chip, as_on_tpu):
    """16 heads of 64 by 1,024, 8 rows: the training cell's attention."""
    from apex_tpu.ops.flash_attention import flash_attention
    x = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    text = _compiled(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    for name in ("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel"):
        assert _kernels(text, name), name


@pytest.mark.parametrize("slots", [8, 32])
def test_decode_kernel_at_gpt2_xl(one_chip, as_on_tpu, slots):
    """25 heads of 64 over 1,024 cached positions plus the token's own:
    8 slots as the cells run, 32 as the issue asked."""
    from apex_tpu.ops.decode_attention import cached_attention
    q = jax.ShapeDtypeStruct((slots, 1, 25, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((slots, 1025, 25, 64), jnp.bfloat16,
                              sharding=one_chip)
    bias = jax.ShapeDtypeStruct((slots, 1025), jnp.float32,
                                sharding=one_chip)
    text = _compiled(lambda q, k, v, b: cached_attention(q, k, v, kv_bias=b),
                     q, kv, kv, bias)
    assert _kernels(text, "_decode_kernel")


@pytest.mark.parametrize("rows,hidden", [(8192, 1024), (256, 1600),
                                         (8, 1600)])
def test_layer_norm_kernels(one_chip, as_on_tpu, rows, hidden):
    """1,024 wide over a training step's tokens; 1,600 wide over a
    prefill chunk and over a decode step's 8 slots."""
    from apex_tpu.normalization import fused_layer_norm_affine
    x = jax.ShapeDtypeStruct((rows, hidden), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((hidden,), jnp.float32, sharding=one_chip)

    def loss(x, w, b):
        return fused_layer_norm_affine(x, w, b, (hidden,), 1e-5,
                                       True).astype(jnp.float32).sum()

    text = _compiled(jax.grad(loss, argnums=(0, 1, 2)), x, w, w)
    assert _kernels(text, "_ln_fwd_kernel") and _kernels(text,
                                                         "_ln_bwd_kernel")


def test_adam_kernel_at_355m_parameters(one_chip, as_on_tpu):
    from apex_tpu import optimizers
    from benchmarks.harness import spec
    cell = spec.load_cell("gpt2m-train-1chip")
    n = cell.reference().total_params(cell.config)
    assert 354_000_000 < n < 356_000_000
    opt = optimizers.FusedAdam(lr=3e-4)
    p = {"w": jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)}
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(opt.init, p))
    text = _compiled(lambda p, g, s: opt.step(p, g, s), p, p, state)
    assert _kernels(text, "_adam_kernel")
