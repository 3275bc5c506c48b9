"""Minimal DistributedDataParallel usage — explicit-collectives style.

Port of the reference's ``examples/simple/distributed/distributed_data_parallel.py``
(init process group from --local_rank, wrap model in DDP, train). The TPU
re-design: ONE process drives every chip; the "process group" is a mesh
axis, and DDP's contract (each replica computes grads on its shard, then
all replicas hold the world-averaged gradient) runs inside ``shard_map``
where the axis name is bound, via ``ddp.reduce_gradients``.

The same model trained under plain GSPMD jit (no shard_map, XLA inserts
the collective from the loss mean) gives identical results — this example
shows the *explicit* style with apex numeric knobs
(``allreduce_always_fp32``, ``gradient_predivide_factor``).

Run: ``python distributed_data_parallel.py`` (uses all visible devices;
set ``XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu``
to simulate 8 chips on CPU).
"""

import argparse
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

shard_map = jax.shard_map

from apex_tpu import amp, parallel
from apex_tpu.models import MLP
from apex_tpu.utils.compile_cache import enable_compile_cache


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--b", type=int, default=256, help="global batch size")
    p.add_argument("--opt-level", default="O2",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--allreduce-always-fp32", action="store_true")
    p.add_argument("--gradient-predivide-factor", type=float, default=1.0)
    p.add_argument("--zero2", action="store_true",
                   help="ZeRO-2: replace the grad all-reduce with "
                   "parallel.zero2_update's reduce-scatter into this "
                   "device's FusedAdam shard (fp32 FusedAdam path; "
                   "the DDP numeric knobs and --opt-level apply to "
                   "the default path only)")
    args = p.parse_args()
    enable_compile_cache()
    if args.zero2 and (args.allreduce_always_fp32
                       or args.gradient_predivide_factor != 1.0):
        p.error("--zero2 bypasses ddp.reduce_gradients, so "
                "--allreduce-always-fp32/--gradient-predivide-factor "
                "would silently do nothing — drop them or the flag")

    devices = jax.devices()
    mesh = Mesh(np.array(devices), axis_names=("data",))
    world = len(devices)
    print(f"world size: {world}")

    model, optimizer = amp.initialize(
        MLP(features=(256, 256)), optax.sgd(0.05), opt_level=args.opt_level,
        verbosity=0)
    ddp = parallel.DistributedDataParallel(
        model,
        allreduce_always_fp32=args.allreduce_always_fp32,
        gradient_predivide_factor=args.gradient_predivide_factor,
        process_group="data")

    params = ddp.init(jax.random.PRNGKey(0), jnp.ones((1, 784)))
    opt_state = optimizer.init(params)

    @jax.jit
    @partial(shard_map, mesh=mesh,
             in_specs=(P(), P(), P("data"), P("data")),
             out_specs=(P(), P(), P()),
             check_vma=False)
    def train_step(params, opt_state, x, y):
        # per-replica forward/backward on the local batch shard
        def loss_fn(p):
            logits = ddp.apply(p, x).astype(jnp.float32)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            with amp.scale_loss(loss, opt_state) as scaled:
                return scaled, loss
        (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        # the DDP contract: world-averaged grads on every replica
        grads = ddp.reduce_gradients(grads)
        params, opt_state = optimizer.step(params, grads, opt_state)
        return params, opt_state, jax.lax.pmean(loss, "data")

    if args.zero2:
        # ZeRO-2 variant: same explicit shard_map style, but the DDP
        # all-reduce disappears — zero2_update's reduce-scatter IS the
        # gradient reduction, the update runs on this device's 1/n
        # flat-buffer slice, and fresh params ride one all-gather.
        # fp32 (amp's skip/scale protocol also composes — zero2_update
        # takes scale=/skip= — but this example keeps the memory story
        # undiluted).
        from jax.sharding import NamedSharding
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.optimizers.fused_adam import FusedAdamState

        # use_pallas left at None: auto-selects the fused kernel on
        # TPU (zero2_update runs it on the local shard), jnp on CPU
        opt2 = FusedAdam(lr=1e-3)
        state0 = opt2.init(params)
        spec = state0.spec

        @jax.jit
        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), P("data"), P("data"), P(), P("data"),
                           P("data")),
                 out_specs=(P(), P("data"), P("data"), P(), P()),
                 check_vma=False)
        def train_step_z2(variables, m, v, c, x, y):
            def loss_fn(p):
                logits = model.apply(p, x).astype(jnp.float32)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, y).mean()
            loss, grads = jax.value_and_grad(loss_fn)(variables)
            st = FusedAdamState(step=c, m=m, v=v, spec=spec)
            variables, st = parallel.zero2_update(
                opt2, variables, grads, st, "data")
            return (variables, st.m, st.v, st.step,
                    jax.lax.pmean(loss, "data"))

        shard = NamedSharding(mesh, P("data"))
        m_s = jax.device_put(state0.m, shard)
        v_s = jax.device_put(state0.v, shard)
        c_s = state0.step
        rng = np.random.RandomState(0)
        with mesh:
            for i in range(args.iters):
                x = jnp.asarray(rng.randn(args.b, 784).astype(np.float32))
                y = jnp.asarray(rng.randint(0, 10, args.b).astype(np.int32))
                params, m_s, v_s, c_s, loss = train_step_z2(
                    params, m_s, v_s, c_s, x, y)
                if i % 5 == 0:
                    print(f"iter {i}: loss {float(loss):.4f}  "
                          f"[zero-2: m/v sharded "
                          f"{m_s.sharding.spec}]")
        return

    rng = np.random.RandomState(0)
    for i in range(args.iters):
        x = jnp.asarray(rng.randn(args.b, 784).astype(np.float32))
        y = jnp.asarray(rng.randint(0, 10, args.b).astype(np.int32))
        params, opt_state, loss = train_step(params, opt_state, x, y)
        if i % 5 == 0:
            print(f"iter {i}: loss {float(loss):.4f}  "
                  f"loss_scale {float(optimizer.loss_scale(opt_state)):.0f}")


if __name__ == "__main__":
    main()
