"""Test harness config: run everything on a virtual 8-device CPU mesh.

The sandbox has no accelerator; sharding/collective tests run against
XLA's host-platform device partitioning (the driver separately dry-runs
the multi-chip path via __graft_entry__.dryrun_multichip).  The platform
and the device count are set in the environment before jax is imported;
nothing else is needed.  The chip is reached through ``chip_smoke.py``.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

assert len(jax.devices()) >= 8, (
    f"test harness expected >=8 CPU devices, got {jax.devices()}")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


# -- smoke tier -----------------------------------------------------------
# `pytest -m smoke`: one happy-path test per subsystem, < 5 min on the
# 2-core CI box, for when the full suite is too long to wait for.
# Centralized here (not per-file decorators) so the set is auditable in
# one place; (file-suffix, exact test name incl. params) pairs.
SMOKE = {
    ("test_amp_levels.py",
     "test_O2_canonical_fp32_masters_compute_half_except_bn"),
    ("test_o1_enforcement.py",
     "test_fp32_ops_run_fp32_while_matmuls_run_half"),
    ("test_loss_scaler.py", "test_full_protocol_inside_jit"),
    ("test_fused_adam.py", "test_matches_numpy_reference[0.0-False]"),
    ("test_fused_lamb.py", "test_matches_numpy_reference"),
    ("test_fused_layer_norm.py",
     "test_forward_matches_reference[shape0-16-False]"),
    ("test_flash_attention.py", "test_matches_reference[False-32]"),
    ("test_flatten.py", "test_roundtrip"),
    ("test_native_ops.py", "test_flatten_unflatten_roundtrip[float32]"),
    ("test_multi_tensor.py", None),   # None = first collected test
    ("test_rnn.py", None),
    ("test_checkpoint.py", "test_roundtrip_preserves_amp_state"),
    ("test_models.py", "test_resnet_forward_shapes"),
    ("test_gpt.py", "test_forward_shape_and_dtype"),
    ("test_ddp.py", "test_reduce_gradients_mean"),
    ("test_syncbn.py", "test_welford_combine_exact"),
    ("test_tensor_parallel.py", "test_tp_forward_matches_replicated"),
    ("test_zero.py", "test_zero2_skip_step"),
    ("test_moe_ep.py", "test_capacity_matches_dense_no_drop"),
    ("test_sequence_parallel.py",
     "test_matches_reference[False-ulysses_attention]"),
    ("test_pipeline.py", "test_forward_matches_sequential[4]"),
    ("test_gpt_pipeline.py",
     "test_pipelined_gpt_forward_matches_monolithic"),
    ("test_kv_cache.py", "test_write_prefill_then_gather_roundtrip"),
    ("test_serving_engine.py",
     "test_cached_decode_matches_full_recompute"),
    ("test_resilience.py", "test_crash_resume_bit_parity[5]"),
    ("test_observability.py", "test_histogram_quantiles_match_sample_oracle"),
    ("test_serving_faults.py", "test_never_fits_prompt_fails_alone"),
    ("test_overload.py", "test_breaker_transitions_on_injected_clock"),
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "smoke: <5-min happy-path tier (one test per "
        "subsystem), run instead of the full suite when that is too "
        "long to wait for")
    config.addinivalue_line(
        "markers", "serving: apex_tpu.serving inference-path tests "
        "(KV cache, decode engine, continuous-batching scheduler); "
        "unmarked slow-wise, so they stay in the tier-1 'not slow' "
        "selection")
    config.addinivalue_line(
        "markers", "chaos: seeded randomized fault-composition soaks "
        "(apex_tpu.resilience.chaos); the build-matrix chaos axis "
        "runs the full-length version via tools/chaos_soak.py")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 'not slow' "
        "selection (its wall budget is already saturated); every "
        "slow-marked test still runs in full on its build-matrix "
        "axis (tests/build_matrix/run.sh invokes the file without "
        "the marker filter)")


def pytest_collection_modifyitems(config, items):
    first_in_file = set()
    matched = set()
    seen_files = set()
    for item in items:
        fname = item.path.name if hasattr(item, "path") else ""
        seen_files.add(fname)
        name = item.name
        if (fname, name) in SMOKE:
            matched.add((fname, name))
            item.add_marker(pytest.mark.smoke)
        elif (fname, None) in SMOKE and fname not in first_in_file:
            first_in_file.add(fname)
            matched.add((fname, None))
            item.add_marker(pytest.mark.smoke)
    # a renamed/reparametrized test must not silently drop its
    # subsystem out of the smoke gate. Enforced only on actual smoke
    # invocations (`-m smoke`) over files that were collected, so
    # node-id-filtered and partial-directory runs don't trip it.
    if "smoke" in (getattr(config.option, "markexpr", "") or ""):
        stale = {(f, n) for f, n in SMOKE
                 if f in seen_files and (f, n) not in matched}
        assert not stale, (
            f"SMOKE entries matched no collected test (renamed?): {stale}")


@pytest.fixture(autouse=True)
def _isolate_amp_state():
    """amp.initialize(O1) installs process-global op patches (by design —
    the reference patches torch namespaces the same way). Tests must not
    leak that policy into each other: deactivate after every test."""
    yield
    try:
        from apex_tpu.amp._amp_state import _amp_state
        _amp_state.opt_properties = None
        _amp_state.casts_disabled = False
    except Exception:
        pass
