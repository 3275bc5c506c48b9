"""``chip_smoke.py`` and the device boundary, as far as a CPU can check.

The chip run itself is ``python chip_smoke.py`` on a TPU.  Here:

- the phase functions run tiny on the CPU mesh, so that a control-flow
  bug costs no chip time (the kernels phase in interpret mode, the
  others through the auto gates' off-TPU answer);
- the script refuses to run without a TPU, naming what it found;
- the data-parallel step lowers FOR a TPU on a four-device mesh with
  its Mosaic calls: the cross-lowering check that needs no chip
  (``on_tpu`` patched true, ``lower(lowering_platforms=("tpu",))``) and
  fails at the parent commit with "Mosaic kernels cannot be
  automatically partitioned";
- the compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else
  to ``<checkout>/.jax_cache``;
- the native library is rebuilt when its source changes.
"""

import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import chip_smoke
from apex_tpu import models

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = models.GPTConfig(
    vocab_size=512, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=2, intermediate_size=128,
    max_position_embeddings=128, hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0)
# ln(512) = 6.24
TINY_TRAIN = dict(seq=128, steps=2, kernels=(), step0_loss=(5.5, 7.0),
                  sync_tol=None)


def test_kernels_phase_interpret_mode():
    """The phase's control flow with its two new rows,
    ``cached_attention`` on a bf16 and on an int8 pool; the other
    kernels' interpret-mode parity has its own L0 files."""
    out = {}
    chip_smoke.phase_kernels(out, decode_shape=(2, 128, 2, 64), checks=())
    assert out["rows"] == 2 and not out["failed"]


def test_trainer_phase_tiny_on_four_devices():
    """The four-device phase runs the one-device phase's code over a
    mesh and adds the one-device oracle, so it covers both."""
    out = {}
    chip_smoke.phase_trainer4(out, TINY, batch=8, **TINY_TRAIN)
    assert out["losses"][-1] < out["losses"][0]
    assert out["batch_shards"] == 4
    assert out["oracle_diff"] <= out["oracle_tol"]
    assert out["psum_g_groups"] == [3.0, 3.0, 7.0, 7.0]


def test_server_phases_tiny(monkeypatch):
    """The server phase under the CHIP's donation policy: the engine
    donates the KV pool of its sampled programs only off the CPU
    backend, so a stale reference to a donated pool would otherwise
    first show on the chip."""
    import apex_tpu.serving.engine as engine

    class ChipJax:
        default_backend = staticmethod(lambda: "tpu")

        def __getattr__(self, name):
            return getattr(jax, name)

    monkeypatch.setattr(engine, "jax", ChipJax())
    params = models.GPTLMHeadModel(TINY).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    out = {}
    chip_smoke.phase_server(
        out, TINY, params, prompt_lens=(5, 16, 32, 40, 70, 90),
        max_new=8, max_batch_size=4, shared_prefix=32,
        expect_mosaic=False)
    assert out["requests"] == 9 and out["prefix_cow_blocks"] >= 1
    assert out["logit_gap_max"] <= out["logit_gap_tol"]


def test_script_refuses_without_tpu(monkeypatch, capsys):
    """``python chip_smoke.py`` where jax finds no TPU (here the CPU
    platform the test harness sets): non-zero exit that names the
    platform, and no result on standard output."""
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code) and "'cpu'" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_last_line_is_the_verdict_with_exactly_its_keys():
    """The driver's chip check reads the last line and refuses any key
    beyond ``ok`` and ``device{platform, kind, count}``; the report
    rides on the line before."""
    dev = jax.devices()[0]
    report, verdict = chip_smoke.result_lines(
        False, dev, 8, {"phases": {"kernels": {"ok": False}}})
    assert "\n" not in report and "\n" not in verdict
    assert json.loads(verdict) == {
        "ok": False, "device": {"platform": dev.platform,
                                "kind": dev.device_kind, "count": 8}}
    assert json.loads(report)["phases"] == {"kernels": {"ok": False}}


def test_data_parallel_step_lowers_for_tpu_with_its_kernels(monkeypatch):
    """Regression for the four-chip bring-up: the whole step inside a
    fully-manual shard_map keeps the Mosaic calls and lowers; the same
    kernels in a GSPMD-sharded jit are refused."""
    from apex_tpu.ops import pallas_utils
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    for mod in ("apex_tpu.ops.flash_attention",
                "apex_tpu.normalization.fused_layer_norm",
                "apex_tpu.optimizers.fused_adam"):
        monkeypatch.setattr(sys.modules[mod], "on_tpu", lambda: True)

    # sequence past FLASH_AUTO_MIN_SEQ so the flash gate picks Pallas
    cfg = models.GPTConfig(
        vocab_size=512, hidden_size=128, num_hidden_layers=1,
        num_attention_heads=2, intermediate_size=256,
        max_position_embeddings=1024, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    model, optimizer, step, _ = chip_smoke.build_trainer(cfg, mesh)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 1024), jnp.int32))["params"])
    opt_state = jax.eval_shape(optimizer.init, params)
    repl = NamedSharding(mesh, P())

    def on(tree, sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    ids = jax.ShapeDtypeStruct((4, 1024), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    text = step.trace(on(params, repl), on(opt_state, repl), ids).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 6
    for name in chip_smoke.TRAIN_KERNELS:
        assert f'kernel_name = "{name}"' in text, name

    from apex_tpu.normalization import fused_layer_norm_affine
    x = jax.ShapeDtypeStruct((8, 128), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    gspmd = jax.jit(lambda x: fused_layer_norm_affine(
        x, jnp.ones((128,)), jnp.zeros((128,)), (128,), 1e-5, True))
    with pytest.raises(NotImplementedError,
                       match="automatically partitioned"):
        gspmd.trace(x).lower(lowering_platforms=("tpu",))


def test_tensor_parallel_server_refuses_on_tpu(monkeypatch):
    """The GSPMD decode path cannot carry its kernels on several TPU
    devices yet: construction raises with the cause instead of failing
    in the first launch or serving from the jnp references."""
    import apex_tpu.serving.engine as engine

    monkeypatch.setattr(engine, "on_tpu", lambda: True)
    params = models.GPTLMHeadModel(TINY).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    with pytest.raises(NotImplementedError, match="shard_map"):
        engine.DecodeEngine(TINY, params, mesh=mesh)


def test_compile_cache_placement(monkeypatch, tmp_path):
    from apex_tpu.utils import compile_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates

    updates.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(ROOT, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert updates["jax_compilation_cache_dir"] == want


def test_native_library_rebuilds_when_source_changes(monkeypatch,
                                                     tmp_path):
    from apex_tpu.ops import native

    if not native.available:
        pytest.skip("native host library failed to build")
    built = []

    def fake_build(src, lib_path):     # g++ stand-in: the real library
        built.append(lib_path)
        shutil.copy(native._lib_path(), lib_path)
        return True

    monkeypatch.setattr(native, "_build", fake_build)
    src = tmp_path / "host_ops.cpp"
    shutil.copy(native._SRC, src)
    first = native._lib_path(str(src), str(tmp_path))
    assert native._open_or_build(str(src), str(tmp_path)) is not None
    assert native._open_or_build(str(src), str(tmp_path)) is not None
    assert built == [first]             # the second call hit the cache

    with open(src, "a") as f:
        f.write("\n// changed\n")
    second = native._lib_path(str(src), str(tmp_path))
    assert second != first
    assert native._open_or_build(str(src), str(tmp_path)) is not None
    assert built == [first, second]
    assert not os.path.exists(first)    # the stale library is gone
