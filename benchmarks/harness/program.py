"""The only place the benchmark touches the system under test: its
entries (``chip_smoke.build_trainer``, ``InferenceServer``), its
compile-cache rule and the layout of its optimizer state.  How its
configuration object is made from a configuration's keys is the
configuration's own ``"program"`` file (``Cell.model_config``).
Everything it hands back is the program's own object; the yardstick
(traffic, reference, reduction, comparison) is elsewhere.
"""

import sys

from benchmarks.harness.spec import ROOT


def import_program():
    """The program's modules; an ``ImportError`` where the checkout
    holds only the benchmark."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import chip_smoke
    from apex_tpu import models, serving
    from apex_tpu.utils.compile_cache import enable_compile_cache
    return chip_smoke, models, serving, enable_compile_cache


def devices_for(cell, require_chip=True):
    """The chips the cell asks for, or ``SystemExit`` before any result.
    ``require_chip=False`` is for the CPU tests alone: the command line
    cannot reach it."""
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise SystemExit(f"{cell.name}: no TPU here, jax found "
                         f"{devs[0].platform!r}; this benchmark does not "
                         "fall back")
    if len(devs) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chips, jax found "
                         f"{len(devs)}")
    return devs[:cell.chips]


def adam_moment_norms(opt_state, scale):
    """Per-leaf norms of ``scale`` times the first moment held in the
    program's flat Adam state (``AmpOptimizerState.inner.m`` laid out by
    its ``FlatSpec``): after one step from zero moments that is the
    gradient as the optimizer got it."""
    import jax
    import jax.numpy as jnp
    inner = opt_state.inner
    spec = inner.spec
    index = jax.tree_util.tree_unflatten(spec.treedef,
                                         list(range(len(spec.shapes))))
    paths = {i: "/".join(k.key for k in path) for path, i in
             jax.tree_util.tree_leaves_with_path(index)}

    @jax.jit
    def norms(m):
        out = {}
        for i, (off, shape) in enumerate(zip(spec.offsets, spec.shapes)):
            n = 1
            for s in shape:
                n *= s
            out[paths[i]] = scale * jnp.sqrt(jnp.sum(jnp.square(
                jax.lax.dynamic_slice_in_dim(m, off, n))))
        return out

    return norms(inner.m)


def memory_peak_bytes(devices):
    """The peak on the fullest chip.  The CPU backend (the tests)
    reports none, and the process's own peak stands in there."""
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices
             if d.memory_stats()]
    if peaks:
        return max(peaks)
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
