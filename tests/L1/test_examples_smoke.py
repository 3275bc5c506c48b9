"""End-to-end smoke runs of every example entry point (subprocess, CPU,
tiny shapes): the reference exercises its examples as L1 harness bodies
(``tests/L1/common/main_amp.py`` IS the imagenet example); here each
``main_amp.py`` must run a few real steps and exit cleanly, so CLI
plumbing (flags like --remat / --ring-attention), amp wiring, and the
train loops can't bit-rot invisibly.
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _run(rel, *args, ndev=None, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if ndev and "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={ndev}").strip()
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, rel), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=ROOT)
    assert r.returncode == 0, f"{rel} failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


def test_simple_main_amp():
    out = _run("examples/simple/main_amp.py", "--epochs", "1",
               "--batch-size", "32", "--opt-level", "O1")
    assert "loss" in out.lower()


@pytest.mark.parametrize("extra", [[], ["--zero2"]],
                         ids=["ddp", "zero2"])
def test_simple_distributed_ddp(extra):
    out = _run("examples/simple/distributed/distributed_data_parallel.py",
               "--iters", "4", "--b", "16", *extra, ndev=8)
    assert "loss" in out.lower()
    if extra:
        assert "zero-2" in out.lower()


def test_dcgan_multi_loss():
    # the example enforces the DCGAN-canonical 64x64 input
    out = _run("examples/dcgan/main_amp.py", "--iters", "3", "--b", "4",
               "--opt-level", "O2")
    assert "loss_d" in out.lower() or "loss" in out.lower()


@pytest.mark.parametrize("extra", [[], ["--remat"], ["--moe", "4"],
                                   ["--remat", "--moe", "4"],
                                   ["--grad-accum", "2"]],
                         ids=["plain", "remat", "moe", "remat_moe",
                              "grad_accum"])
def test_bert_tiny(extra):
    # b=16: the grad-accum microbatch (b/2) must still divide the device
    # count the subprocess may inherit (up to 8)
    out = _run("examples/bert/main_amp.py", "--config", "tiny", "--b", "16",
               "--seq-len", "32", "--steps", "3", *extra)
    assert "loss" in out.lower()


def test_imagenet_zero_sharded_opt_state(tmp_path):
    out = _run("examples/imagenet/main_amp.py", "--epochs", "1", "--b", "16",
               "--arch", "resnet18", "--image-size", "32", "--num-classes",
               "3", "--steps-per-epoch", "3", "--val-steps", "1",
               "--workers", "2", "--zero", "--checkpoint-dir",
               str(tmp_path), ndev=8)
    assert "Prec@1" in out
    # the unshard-on-save branch ran and produced a checkpoint
    assert "saved checkpoint" in out
    assert any(p.name.startswith("last") for p in tmp_path.iterdir())


def test_bert_tiny_ring_attention():
    out = _run("examples/bert/main_amp.py", "--config", "tiny", "--b", "8",
               "--seq-len", "32", "--steps", "3", "--ring-attention", "2",
               ndev=8)
    assert "loss" in out.lower()


@pytest.mark.parametrize("extra", [[], ["--grad-accum", "2"],
                                   ["--moe", "4"]],
                         ids=["plain", "grad_accum", "moe"])
def test_bert_tiny_pp_1f1b(extra):
    """dp x pp with the interleaved memory-bounded schedule: the manual
    loss-and-grad path under amp O2 + FusedLAMB + dynamic scaling,
    with and without the unscale-with-stashed accumulation protocol."""
    out = _run("examples/bert/main_amp.py", "--config", "tiny", "--b", "16",
               "--seq-len", "32", "--steps", "3", "--pp", "2",
               "--pp-microbatches", "2", "--pp-schedule", "1f1b", *extra,
               ndev=8)
    assert "loss" in out.lower()


def test_bert_tiny_pp_1f1b_ulysses_sp():
    """dp x sp x pp on the interleaved schedule through the example CLI:
    --sp-attention ulysses is the SP pattern 1F1B can host (ring is
    rejected with a pointer to the repro — see the arg's help)."""
    out = _run("examples/bert/main_amp.py", "--config", "tiny", "--b", "8",
               "--seq-len", "32", "--steps", "3", "--pp", "2",
               "--pp-microbatches", "2", "--pp-schedule", "1f1b",
               "--ring-attention", "2", "--sp-attention", "ulysses",
               ndev=8)
    assert "loss" in out.lower()


@pytest.mark.parametrize(
    "extra",
    [[], ["--flash"],
     ["--sp", "2", "--sp-attention", "ulysses"],
     # vp-CE path: O0 because half precision inside the partial-manual
     # region is the known CPU-backend limitation (TPU compiles it)
     ["--tp", "2", "--opt-level", "O0"],
     ["--tp", "2"]],              # dense-loss fallback + warning path
    ids=["plain", "flash", "ulysses_sp", "tp_vp", "tp_dense_fallback"])
def test_gpt_tiny(extra):
    out = _run("examples/gpt/main_amp.py", "--config", "tiny", "--b", "8",
               "--seq-len", "32", "--steps", "3", *extra, ndev=8)
    assert "loss" in out.lower()
