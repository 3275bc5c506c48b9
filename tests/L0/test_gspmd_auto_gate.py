"""Pallas kernels must step aside under GSPMD-automatic axes.

Round-5 live-hardware finding (one v5e): inside
a partial-manual ``shard_map`` region — pipelined Megatron TP, where the
model axis stays automatic so XLA inserts the TP collectives — the SPMD
partitioner rejects Mosaic custom calls outright::

    NotImplementedError: Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map.

The CPU tiers never see this because the off-TPU gates already pick the
jnp paths.  ``ops.pallas_utils.gspmd_auto_axes`` is the trace-time
detector; every kernel's ``use_pallas=None`` auto gate consults it.
These tests pin (a) the detector's verdict in each tracing regime and
(b) that the gates actually reroute, by forcing ``on_tpu`` True and
booby-trapping the kernel entry points.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.ops.pallas_utils import gspmd_auto_axes

pytestmark = pytest.mark.smoke


def _mesh():
    dev = np.array(jax.devices()[:4]).reshape(2, 2)
    return Mesh(dev, ("data", "model"))


@pytest.mark.parametrize("s,narrow,wide", [
    # length; the block where a head's row is at most 256 bytes, as 64
    # or 128 in bfloat16 (up to 1,024: a grid step costs what it costs
    # whatever its size, PR 28's sweep on one v5e); the block of wider
    # rows (up to 512, what every caller had before: at 128 in float32
    # the dkv kernel with dropout does not fit VMEM at 1,024)
    (1, 128, 128),
    (64, 128, 128),
    (128, 128, 128),
    (200, 256, 256),
    (384, 384, 384),
    (512, 512, 512),
    (640, 640, 320),      # 5*128: the whole length, or its half
    (768, 768, 384),      # not 512: a 512 block would pad 768 to 1,024
    (896, 896, 320),      # 7*128: 320 pads by 64, within an eighth
    (1024, 1024, 512),    # the training cells: one block a head
    (1152, 384, 384),     # 9*128: the widest exact divisor
    (1536, 768, 512),
    (1664, 896, 256),     # 13*128 has no wide divisor: bounded re-pad
    (2048, 1024, 512),
    (4096, 1024, 512),
    (16384, 1024, 512),
])
def test_default_block_by_shape(s, narrow, wide):
    """The adaptive flash tile default must never induce significant
    padding beyond the 128 grain: the chosen block divides the
    128-padded sequence exactly when any wide candidate can, and may
    otherwise re-pad by at most 1/8 of the work (a 512 block at S=768
    would silently run 1.78x the real FLOPs and stays rejected, while
    1664 = 13*128 with no wide divisor at all escapes the 128-tile
    floor for a few percent of masked padding)."""
    from apex_tpu.ops.flash_attention import _default_block

    for want, d, itemsize in ((narrow, 64, 2), (narrow, 128, 2),
                              (narrow, 64, 4), (wide, 128, 4),
                              (wide, 256, 2)):
        assert _default_block(s, d, itemsize) == want, (d, itemsize)
        sp = -(-s // 128) * 128
        assert (-(-sp // want) * want) - sp <= sp // 8
        assert 128 <= want <= 1024


def test_auto_gate_warns_once_on_tpu_downgrade(monkeypatch):
    """On TPU under GSPMD-automatic axes the gate must say WHY the
    kernels vanished — once, naming the axes (ADVICE round 5: users
    otherwise read jnp-reference throughput as kernel throughput)."""
    import warnings

    import apex_tpu.ops.pallas_utils as pu

    monkeypatch.setattr(pu, "on_tpu", lambda: True)
    monkeypatch.setattr(pu, "gspmd_auto_axes", lambda: ("model",))
    monkeypatch.setattr(pu, "_warned_auto_downgrade", False)
    with pytest.warns(RuntimeWarning, match=r"model"):
        assert pu.pallas_auto_gate() is False
    with warnings.catch_warnings():
        warnings.simplefilter("error")        # second call: silent
        assert pu.pallas_auto_gate() is False
    # an explicit flag bypasses both the gate and the warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        monkeypatch.setattr(pu, "_warned_auto_downgrade", False)
        assert pu.pallas_auto_gate(True) is True


def test_detector_outside_any_mesh():
    assert not gspmd_auto_axes()
    seen = []
    jax.jit(lambda x: (seen.append(gspmd_auto_axes()), x)[1])(jnp.ones(3))
    assert seen == [()]


def test_detector_full_manual_vs_partial_manual():
    mesh = _mesh()
    seen = {}

    def full(x):
        seen["full"] = bool(gspmd_auto_axes())
        return x

    def partial(x):
        seen["partial"] = bool(gspmd_auto_axes())
        return x

    with mesh:
        jax.jit(jax.shard_map(full, mesh=mesh, in_specs=P(), out_specs=P()))(
            jnp.ones(8))
        jax.jit(jax.shard_map(partial, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), axis_names={"data"},
                              check_vma=False))(jnp.ones(8))
    # fully-manual regions keep the real kernels; partial-manual (an
    # Auto axis remains) must reroute
    assert seen == {"full": False, "partial": True}


def test_detector_gspmd_jit_under_mesh_scopes():
    """A plain ``jax.jit`` under a mesh scope of several devices is
    sharded by GSPMD, and the Mosaic lowering refuses it ("cannot be
    automatically partitioned") — in the legacy ``with mesh:`` scope,
    which leaves the abstract mesh empty, as much as under
    ``jax.set_mesh``.  A one-device mesh partitions nothing."""
    mesh = _mesh()
    seen = {}

    def probe(tag):
        def f(x):
            seen[tag] = bool(gspmd_auto_axes())
            return x
        return jax.jit(f)

    with mesh:
        probe("legacy")(jnp.ones(8))
    with jax.set_mesh(mesh):
        probe("set_mesh")(jnp.ones(8))
    one = Mesh(np.array(jax.devices()[:1]), ("data",))
    with one:
        probe("legacy_one")(jnp.ones(8))
    with jax.set_mesh(one):
        probe("set_mesh_one")(jnp.ones(8))
    assert seen == {"legacy": True, "set_mesh": True,
                    "legacy_one": False, "set_mesh_one": False}


def _boobytrap(monkeypatch, module, kernel_name):
    """Pretend we are on TPU and make the Pallas entry explode — the
    auto gate must never reach it inside a partial-manual region.  The
    gates resolve via ``pallas_utils.pallas_auto_gate``, so the TPU
    pretence goes on ``pallas_utils.on_tpu``."""
    from apex_tpu.ops import pallas_utils
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)

    def boom(*a, **k):
        raise AssertionError(f"{kernel_name} Pallas path taken under "
                             "GSPMD-automatic axes")
    monkeypatch.setattr(module, kernel_name, boom)


def test_layer_norm_gate_reroutes(monkeypatch):
    import importlib
    # the package re-exports the fused_layer_norm FUNCTION under the
    # submodule's name; fetch the real module
    fln = importlib.import_module("apex_tpu.normalization.fused_layer_norm")

    _boobytrap(monkeypatch, fln, "_ln_fwd_pallas")
    x = jnp.ones((4, 8, 32), jnp.float32)
    w = jnp.ones((32,), jnp.float32)
    b = jnp.zeros((32,), jnp.float32)

    # sanity: outside a mesh the (fake-TPU) gate picks the kernel
    with pytest.raises(AssertionError, match="Pallas path taken"):
        fln.fused_layer_norm_affine(x, w, b, (32,))

    mesh = _mesh()

    def region(x):
        return fln.fused_layer_norm_affine(x, w, b, (32,))

    with mesh:
        out = jax.jit(jax.shard_map(
            region, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            axis_names={"data"}, check_vma=False))(x)
    ref = fln.fused_layer_norm_affine(x, w, b, (32,), use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_flash_gate_reroutes(monkeypatch):
    import importlib
    fa = importlib.import_module("apex_tpu.ops.flash_attention")

    _boobytrap(monkeypatch, fa, "_flash")
    # above FLASH_AUTO_MIN_SEQ — the auto path routes shorter
    # sequences to XLA attention and would never reach the kernel
    q = jnp.ones((2, 1024, 2, 8), jnp.float32) * 0.1
    k, v = q * 0.5, q * 0.25

    with pytest.raises(AssertionError, match="Pallas path taken"):
        fa.flash_attention(q, k, v)

    mesh = _mesh()

    def region(q, k, v):
        return fa.flash_attention(q, k, v)

    with mesh:
        out = jax.jit(jax.shard_map(
            region, mesh=mesh,
            in_specs=(P("data"),) * 3, out_specs=P("data"),
            axis_names={"data"}, check_vma=False))(q, k, v)
    ref = fa.flash_attention(q, k, v, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_fused_adam_gate_reroutes(monkeypatch):
    import apex_tpu.optimizers.fused_adam as fad
    from apex_tpu.ops import pallas_utils

    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)

    def boom(*a, **k):
        raise AssertionError("fused_adam Pallas path taken under "
                             "GSPMD-automatic axes")
    monkeypatch.setattr(fad, "_adam_flat_pallas", boom)

    opt = fad.FusedAdam(lr=1e-3, layout="flat")
    params = {"w": jnp.ones((8, 8), jnp.float32)}
    grads = {"w": jnp.full((8, 8), 0.1, jnp.float32)}
    state = opt.init(params)

    # outside a mesh the (fake-TPU) flat layout picks the kernel
    with pytest.raises(AssertionError, match="Pallas path taken"):
        jax.tree_util.tree_map(
            lambda x: x, opt.step(params, grads, state))

    mesh = _mesh()

    def region(p, g):
        new_p, _ = opt.step(p, g, opt.init(p))
        return new_p

    with mesh:
        out = jax.jit(jax.shard_map(
            region, mesh=mesh,
            in_specs=(P(), P()), out_specs=P(),
            axis_names={"data"}, check_vma=False))(params, grads)
    # jnp fallback: one Adam step moves every weight by ~lr
    assert float(jnp.max(jnp.abs(out["w"] - params["w"]))) > 1e-4
