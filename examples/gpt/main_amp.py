"""Causal-LM pretraining: GPT + causal flash attention + amp O2 + DDP.

The long-context flagship example — the decoder companion to
``examples/bert``. Next-token loss on synthetic token streams (no
downloads; the point is the training machinery). Data-parallel over
all chips: the whole step runs inside one fully-manual ``shard_map``
over the ``data`` axis with an explicit DDP gradient all-reduce, which
is what keeps the Pallas kernels (flash, FusedLayerNorm, FusedAdam) on
several chips — a GSPMD-partitioned jit cannot carry a Mosaic call.
``--flash`` runs the whole stack on the fused causal flash kernel
(O(S) attention memory — the lever that makes ``--seq-len 16384``
trainable); ``--sp SP`` shards the sequence over an SP-way axis (ring
or Ulysses) and ``--tp TP`` shards the weights, both under GSPMD;
``--remat`` trades FLOPs for activation HBM at depth.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu import amp, models, optimizers, parallel
from apex_tpu.utils import AverageMeter, maybe_print
from apex_tpu.utils.compile_cache import enable_compile_cache


def parse_args():
    p = argparse.ArgumentParser(description="GPT causal-LM training (TPU)")
    p.add_argument("--config", default="small",
                   choices=["small", "medium", "tiny"])
    p.add_argument("--b", "--batch-size", type=int, default=8, dest="b")
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--opt-level", default="O2",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--loss-scale", default=None)
    p.add_argument("--print-freq", type=int, default=5)
    p.add_argument("--flash", action="store_true",
                   help="causal flash attention (Pallas on TPU) instead "
                   "of the einsum + fp32-softmax default — O(S) "
                   "attention memory")
    p.add_argument("--sp", type=int, default=0, metavar="SP",
                   help="shard the sequence over SP-way sequence "
                   "parallelism (hybrid DP x SP mesh)")
    p.add_argument("--sp-attention", default="ulysses",
                   choices=("ring", "ulysses"))
    p.add_argument("--tp", type=int, default=0, metavar="TP",
                   help="Megatron tensor parallelism over a TP-way "
                   "model axis (parallel.gpt_tp_rules — vocab-sharded "
                   "tied head; composes with --sp on one mesh)")
    p.add_argument("--remat", action="store_true")
    return p.parse_args()


def main():
    args = parse_args()
    enable_compile_cache()
    cfg = {"small": models.gpt_small(),
           "medium": models.gpt_medium(),
           "tiny": models.GPTConfig(
               vocab_size=997, hidden_size=128, num_hidden_layers=2,
               num_attention_heads=4, intermediate_size=256,
               max_position_embeddings=args.seq_len)}[args.config]
    if cfg.max_position_embeddings < args.seq_len:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, max_position_embeddings=args.seq_len)
    if args.remat:
        import dataclasses
        cfg = dataclasses.replace(cfg, remat=True)
    # vocab-parallel CE is partial-manual shard_map; half-precision
    # compute inside that region trips this jax build's XLA CPU
    # backend ("Invalid binary instruction opcode copy" — the same
    # documented limitation as PipelinedBert's tp_axis). The TPU
    # backend compiles it; on CPU demo runs use O0 or the dense loss.
    use_vp = bool(args.tp) and (jax.devices()[0].platform == "tpu"
                                or args.opt_level == "O0")
    true_vocab = cfg.vocab_size
    if use_vp and cfg.vocab_size % (args.tp * 128):
        # Megatron's make_vocab_size_divisible_by move: GPT-2's 50257
        # divides nothing — pad the embedding rows to 128*tp lanes so
        # the vocab-parallel CE can shard them (padding rows are
        # -inf-masked in the loss, so numerics are the true-vocab
        # loss; the dense fallback path keeps the TRUE vocab — padded
        # garbage rows would leak probability mass into its softmax)
        import dataclasses
        unit = args.tp * 128
        cfg = dataclasses.replace(cfg, vocab_size=-(-cfg.vocab_size
                                                    // unit) * unit)

    devices = jax.devices()
    n_dev = len(devices)
    sp, tp = args.sp, args.tp
    model_par = (sp or 1) * (tp or 1)
    if n_dev % model_par:
        raise SystemExit(f"--sp {sp} x --tp {tp} must divide the "
                         f"device count ({n_dev})")
    if sp and args.seq_len % sp:
        raise SystemExit(f"--sp {sp} must divide --seq-len "
                         f"({args.seq_len})")
    dp = n_dev // model_par
    shape, names = [dp], ["data"]
    if sp:
        shape.append(sp)
        names.append("sp")
    if tp:
        shape.append(tp)
        names.append("model")
    mesh = Mesh(np.array(devices).reshape(shape), tuple(names))
    if args.b % dp:
        raise SystemExit(f"batch {args.b} must divide by dp={dp}")
    maybe_print(f"devices: {n_dev} (dp={dp}, sp={sp or 1}, "
                f"tp={tp or 1}), config: {args.config}, "
                f"seq: {args.seq_len}, flash: {args.flash}", rank0=True)

    attention_fn = None
    if sp:
        from apex_tpu.parallel import (make_ring_attention,
                                       make_ulysses_attention)
        make = (make_ulysses_attention if args.sp_attention == "ulysses"
                else make_ring_attention)
        sp_fn = make("sp", causal=True)

        def attention_fn(q, k, v, bias=None, dropout_fn=None):
            if bias is None:
                bias = jnp.zeros((q.shape[0], 1, 1, q.shape[1]),
                                 jnp.float32)
            f = jax.shard_map(
                lambda q, k, v, b: sp_fn(q, k, v, bias=b,
                                         dropout_fn=dropout_fn),
                mesh=mesh,
                in_specs=(P("data", "sp"),) * 3
                + (P("data", None, None, "sp"),),
                out_specs=P("data", "sp"))
            return f(q, k, v, bias)
    elif args.flash:
        from apex_tpu.ops.flash_attention import make_flash_attention
        attention_fn = make_flash_attention(causal=True)

    model, optimizer = amp.initialize(
        models.GPTLMHeadModel(cfg, attention_fn=attention_fn),
        # TP'd params need the per-leaf layout: the flat concat cannot
        # carry Megatron placements (FusedAdam docstring)
        optimizers.FusedAdam(lr=args.lr,
                             layout="tree" if tp else "flat"),
        opt_level=args.opt_level, loss_scale=args.loss_scale)

    rng = np.random.RandomState(0)

    def batches():
        while True:
            yield rng.randint(0, true_vocab,
                              (args.b, args.seq_len)).astype(np.int32)

    # dp-sized init dummy: a full-batch init would materialize the
    # (B, S, V) fp32 logits on ONE device — at --seq-len 16384 that is
    # ~26 GB before training starts (same trick as examples/bert)
    ids0 = jnp.ones((dp, args.seq_len), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids0)["params"]
    opt_state = optimizer.init(params)
    shard = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())
    grad_specs = None
    if tp:
        grad_specs = parallel.param_specs(
            params, mesh, parallel.gpt_tp_rules("model"))
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, grad_specs)
        # per-leaf moments inherit each param's Megatron placement by
        # path suffix, then add ZeRO-1 data sharding on top
        opt_state = parallel.shard_optimizer_state(
            opt_state, mesh, axis="data", like_params=params)
    else:
        params = jax.device_put(params, repl)
        opt_state = jax.device_put(opt_state, repl)

    if tp and not use_vp:
        maybe_print(
            f"--tp {tp}: vocab-parallel CE disabled under "
            f"{args.opt_level} on the {jax.devices()[0].platform} "
            "backend (half-precision inside partial-manual shard_map "
            "is the known CPU-backend limitation); dense loss instead",
            rank0=True)

    # pure data parallelism takes the explicit-collectives form: the
    # step is one fully-manual shard_map region, so the kernels lower
    # per device; sp/tp keep the GSPMD jit (kernels give way to jnp
    # there, pallas_utils.pallas_auto_gate)
    ddp = (None if sp or tp
           else parallel.DistributedDataParallel(process_group="data"))

    def step(params, opt_state, ids):
        def loss_fn(p):
            if use_vp:
                # vocab-parallel CE: under TP the (B, S, V) logits are
                # never materialized — each device computes its vocab
                # slice and three (B, S) collectives make the loss
                # (ops.vocab_parallel_lm_loss)
                from apex_tpu import ops
                hidden = model.apply({"params": p}, ids,
                                     return_hidden=True)
                loss = ops.vocab_parallel_lm_loss(
                    hidden, p["wte"]["embedding"], ids, mesh,
                    true_vocab=true_vocab)
            else:
                logits = model.apply({"params": p}, ids)
                loss = models.lm_loss(logits, ids)
            with amp.scale_loss(loss, opt_state) as scaled:
                return scaled, loss
        grads, loss = jax.grad(loss_fn, has_aux=True)(params)
        if ddp is not None:
            # the DDP contract: world-averaged grads on every replica
            grads = ddp.reduce_gradients(grads)
            loss = jax.lax.pmean(loss, "data")
        if grad_specs is not None:
            # pin grads to the Megatron specs so the updated params
            # keep their TP placement across steps (see PipelinedCommon
            # .param_spec_tree for the failure mode)
            grads = jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, s)), grads, grad_specs)
        params, opt_state = optimizer.step(params, grads, opt_state)
        return params, opt_state, loss

    if ddp is not None:
        step = jax.shard_map(step, mesh=mesh,
                             in_specs=(P(), P(), P("data")),
                             out_specs=(P(), P(), P()), check_vma=False)
    train_step = jax.jit(step, donate_argnums=(0, 1))

    meter = AverageMeter()
    with mesh:
        for step, ids in zip(range(args.steps), batches()):
            t0 = time.perf_counter()
            params, opt_state, loss = train_step(
                params, opt_state, jax.device_put(ids, shard))
            loss = float(loss)          # host fetch: waits for the step
            dt = time.perf_counter() - t0
            if step > 0:                # skip compile step
                meter.update(args.b * args.seq_len / dt)
            if step % args.print_freq == 0 or step == args.steps - 1:
                maybe_print(f"step {step:4d} loss {loss:8.4f} "
                            f"tok/s {meter.avg:12.1f}", rank0=True)
    maybe_print(f"final: loss {loss:.4f}, avg {meter.avg:.1f} tok/s",
                rank0=True)


if __name__ == "__main__":
    main()
