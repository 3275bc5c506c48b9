"""BERT pretraining with FusedLAMB + FusedLayerNorm + amp O2 + DDP.

BASELINE.json config 4 — the workload the reference's LAMB and LayerNorm
CUDA kernels exist to serve (they ship with no Python wrapper in the
reference snapshot; apex_tpu provides the full optimizer). Masked-LM +
NSP heads on synthetic data (no downloads): the point is the training
machinery, not GLUE scores.

GSPMD data-parallel over all chips; ``--ring-attention`` demonstrates the
sequence-parallel attention path for long sequences (attention q/k/v
shards rotate around the mesh ring while everything else stays
data-parallel).
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu import amp, models, optimizers
from apex_tpu.ops.pallas_utils import on_tpu
from apex_tpu.utils import AverageMeter, maybe_print
from apex_tpu.utils.compile_cache import enable_compile_cache


def parse_args():
    p = argparse.ArgumentParser(description="BERT pretraining (TPU)")
    p.add_argument("--config", default="base", choices=["base", "large",
                                                        "tiny"])
    p.add_argument("--b", "--batch-size", type=int, default=32, dest="b")
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--max-grad-norm", type=float, default=1.0)
    p.add_argument("--opt-level", default="O2",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--loss-scale", default=None)
    p.add_argument("--mask-prob", type=float, default=0.15)
    p.add_argument("--print-freq", type=int, default=5)
    p.add_argument("--ring-attention", type=int, default=0, metavar="SP",
                   help="shard attention over SP-way sequence parallelism "
                   "(hybrid DP x SP mesh; SP must divide the device count "
                   "and --seq-len)")
    p.add_argument("--sp-attention", default="ring",
                   choices=("ring", "ulysses"),
                   help="sequence-parallel attention pattern under "
                   "--ring-attention: ring (KV rotation, O(S_local) "
                   "memory per hop) or ulysses (all_to_all head "
                   "scatter; the pattern that composes with "
                   "--pp-schedule 1f1b)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize encoder layers in backward "
                   "(jax.checkpoint): ~33%% more FLOPs for O(layers) "
                   "less activation HBM — for long --seq-len")
    p.add_argument("--moe", type=int, default=0, metavar="E",
                   help="replace each layer's MLP with a Switch-MoE of "
                   "E experts (aux load-balance loss auto-added; shard "
                   "experts with models.EP_RULES for EP)")
    p.add_argument("--moe-dispatch", default="dense",
                   choices=["dense", "capacity"],
                   help="MoE dispatch: dense (exact, E x FLOPs) or "
                   "capacity (Switch capacity-factor gather/scatter; "
                   "tokens past capacity ride the residual)")
    p.add_argument("--moe-capacity-factor", type=float, default=1.25)
    p.add_argument("--grad-accum", type=int, default=1, metavar="A",
                   help="accumulate grads over A microbatches per step "
                   "(amp unscale-with-stashed protocol; overflow in ANY "
                   "microbatch skips the whole update)")
    p.add_argument("--pp", type=int, default=0, metavar="S",
                   help="pipeline the encoder over S stages on a "
                   "(data, pipe) mesh (models.PipelinedBert / GPipe); "
                   "S must divide the device count and the layer count")
    p.add_argument("--pp-schedule", default="gpipe",
                   choices=("gpipe", "1f1b"),
                   help="pipeline schedule under --pp: gpipe (autodiff "
                        "through the scan) or 1f1b (interleaved "
                        "fwd/bwd, live activations bounded by the stage "
                        "count; composes with dp, --grad-accum, --moe, "
                        "and --sp-attention ulysses — ring SP needs "
                        "gpipe)")
    p.add_argument("--pp-microbatches", type=int, default=4, metavar="M",
                   help="GPipe microbatches per step under --pp "
                   "(bubble fraction (S-1)/(M+S-1))")
    return p.parse_args()


def get_config(name):
    if name == "base":
        return models.bert_base()
    if name == "large":
        return models.bert_large()
    return models.BertConfig(vocab_size=1024, hidden_size=128,
                             num_hidden_layers=2, num_attention_heads=4,
                             intermediate_size=256,
                             max_position_embeddings=512)


def synthetic_mlm_batch(rng, args, cfg):
    """ids + mask positions + labels, the standard MLM setup."""
    ids = rng.randint(4, cfg.vocab_size, (args.b, args.seq_len))
    labels = ids.copy()
    mask = rng.rand(args.b, args.seq_len) < args.mask_prob
    ids[mask] = 3  # [MASK]
    weights = mask.astype(np.float32)
    nsp = rng.randint(0, 2, (args.b,))
    return (ids.astype(np.int32), labels.astype(np.int32), weights,
            nsp.astype(np.int32))


def main():
    args = parse_args()
    enable_compile_cache()
    cfg = get_config(args.config)
    cfg = dataclasses.replace(cfg, remat=args.remat,
                              moe_experts=args.moe,
                              moe_dispatch=args.moe_dispatch,
                              moe_capacity_factor=args.moe_capacity_factor)

    devices = jax.devices()
    n_dev = len(devices)
    if n_dev > 1 and on_tpu():
        # the step below is a GSPMD-partitioned jit, which cannot carry
        # the Mosaic kernels in it (FusedLayerNorm, flash attention):
        # refuse rather than fail at the first step's lowering or train
        # through the jnp references unannounced
        raise SystemExit(
            f"examples/bert: {n_dev} TPU devices - GSPMD cannot "
            "partition the Pallas kernels of this step (\"Mosaic "
            "kernels cannot be automatically partitioned\"), and the "
            "step is not yet a shard_map region as in examples/gpt "
            "(its masked-LM loss normalizes over the global batch).  "
            "Open work: CHANGES.md PR 21, ROADMAP.md Speed item 10.  "
            "Runs on one chip and on CPU meshes.")
    sp = args.ring_attention
    pp = args.pp
    if pp and sp:
        if n_dev % (sp * pp) or args.seq_len % sp or \
                cfg.num_hidden_layers % pp:
            raise SystemExit(
                f"SP={sp} x PP={pp} must divide devices ({n_dev}), SP "
                f"the seq len ({args.seq_len}), PP the layers "
                f"({cfg.num_hidden_layers})")
        dp = n_dev // (sp * pp)
        mesh = Mesh(np.array(devices).reshape(dp, sp, pp),
                    ("data", "sp", "pipe"))
    elif sp:
        if n_dev % sp or args.seq_len % sp:
            raise SystemExit(f"SP={sp} must divide devices ({n_dev}) and "
                             f"seq len ({args.seq_len})")
        dp = n_dev // sp
        mesh = Mesh(np.array(devices).reshape(dp, sp), ("data", "sp"))
    elif pp:
        if n_dev % pp or cfg.num_hidden_layers % pp:
            raise SystemExit(f"PP={pp} must divide devices ({n_dev}) and "
                             f"layers ({cfg.num_hidden_layers})")
        dp = n_dev // pp
        mesh = Mesh(np.array(devices).reshape(dp, pp), ("data", "pipe"))
    else:
        dp = n_dev
        mesh = Mesh(np.array(devices), ("data",))
    if args.b % dp:
        raise SystemExit(f"batch {args.b} must divide by dp={dp}")
    onef1b = pp and args.pp_schedule == "1f1b"
    if args.pp_schedule == "1f1b" and not pp:
        raise SystemExit("--pp-schedule 1f1b needs --pp S")
    if onef1b and sp and args.sp_attention == "ring":
        raise SystemExit(
            "--pp-schedule 1f1b cannot host ring attention (its "
            "collective-carrying scan miscompiles in the schedule's "
            "branches — tools/repro_ring_1f1b.py); use "
            "--sp-attention ulysses or the gpipe schedule")
    maybe_print(f"devices: {n_dev} (dp={dp}, sp={sp or 1}, pp={pp or 1}), "
                f"config: {args.config}", rank0=True)

    attention_fn = None
    if sp and pp:
        # inside PipelinedBert's shard_map the sp axis is already
        # manual: the adapter runs directly, no inner shard_map
        from apex_tpu.parallel import (make_ring_attention,
                                       make_ulysses_attention)
        attention_fn = (make_ulysses_attention("sp")
                        if args.sp_attention == "ulysses"
                        else make_ring_attention("sp"))
    elif sp:
        from apex_tpu.parallel import (make_ring_attention,
                                       make_ulysses_attention)

        shard_map = jax.shard_map

        ring_fn = (make_ulysses_attention("sp")
                   if args.sp_attention == "ulysses"
                   else make_ring_attention("sp"))

        def attention_fn(q, k, v, bias=None, dropout_fn=None):
            """Hybrid DP x SP: batch stays sharded on `data`, the sequence
            dim of q/k/v (and the key mask) shards over `sp`, and the KV
            shards rotate the ring. Composes under the outer GSPMD jit;
            the bias contract/dropout check lives in the adapter."""
            if bias is None:
                bias = jnp.zeros((q.shape[0], 1, 1, q.shape[1]), jnp.float32)
            f = shard_map(
                lambda q, k, v, bias: ring_fn(q, k, v, bias=bias,
                                              dropout_fn=dropout_fn),
                mesh=mesh,
                in_specs=(P("data", "sp"), P("data", "sp"), P("data", "sp"),
                          P("data", None, None, "sp")),
                out_specs=P("data", "sp"))
            return f(q, k, v, bias)

    if pp:
        # NB: the example trains deterministically (it passes no dropout
        # rngs), so the config's dropout probs are inert here; with
        # rngs={'dropout': ...} PipelinedBert runs them per
        # (microbatch, stage, data-shard)
        # the pipeline sees b/grad_accum examples per call, dp-sharded
        per_call = args.b // max(args.grad_accum, 1) // dp
        if per_call % args.pp_microbatches:
            raise SystemExit(
                f"per-data-shard batch {per_call} (b/grad_accum/dp) must "
                f"divide into --pp-microbatches {args.pp_microbatches}")
        model_def = models.PipelinedBert(
            cfg, mesh, pp=pp, num_microbatches=args.pp_microbatches,
            batch_axis="data", seq_axis="sp" if sp else None,
            attention_fn=attention_fn)
    else:
        model_def = models.BertForPreTraining(cfg,
                                              attention_fn=attention_fn)
    # the BERT recipe: bias/LayerNorm params take no weight decay (param
    # group) AND no layer adaptation (trust ratio 1.0) — the reference's
    # downstream-BERT convention, now expressible declaratively
    optimizer_def = optimizers.FusedLAMB(
        lr=args.lr, max_grad_norm=args.max_grad_norm,
        param_groups=[{"match": r"(bias|_ln)", "weight_decay": 0.0}],
        exclude_from_layer_adaptation=lambda path: any(
            "bias" in str(k) or "_ln" in str(k) for k in path),
        # under --pp stage params are (pp, ...) stacks of per-layer
        # tensors; per-slice ratios keep LAMB's layer-wise adaptation
        # identical to the non-pipelined model
        per_slice_trust_ratio=(
            (lambda path: any("stages" in str(k) for k in path))
            if pp else None))
    model, optimizer = amp.initialize(
        model_def, optimizer_def, opt_level=args.opt_level,
        loss_scale=args.loss_scale)

    # dummy batch must divide over the data axis (attention shard_map)
    ids0 = jnp.zeros((dp, args.seq_len), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids0)["params"]
    opt_state = optimizer.init(params)

    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("data"))
    if pp:  # the model owns its placement (stages on the pipe axis)
        params = model_def.shard_variables({"params": params})["params"]
    else:
        params = jax.device_put(params, repl)
    opt_state = jax.device_put(opt_state, repl)

    def batch_loss(p, ids, labels, weights, nsp, mlm_denom, div):
        """Shared by the plain and grad-accum steps: MLM (weighted by
        mask positions over ``mlm_denom``) + NSP/div + MoE aux/div."""
        if args.moe and pp:
            # PipelinedBert returns the pipeline-accumulated aux as a
            # third output (sow can't escape the pipeline scan)
            mlm_logits, nsp_logits, aux = model.apply(
                {"params": p}, ids, deterministic=True)
        elif args.moe:
            (mlm_logits, nsp_logits), mut = model.apply(
                {"params": p}, ids, deterministic=True,
                mutable=["losses"])
            aux = sum(jnp.sum(leaf) for leaf in
                      jax.tree_util.tree_leaves(mut["losses"]))
        else:
            mlm_logits, nsp_logits = model.apply(
                {"params": p}, ids, deterministic=True)
            aux = 0.0
        mlm_losses = optax.softmax_cross_entropy_with_integer_labels(
            mlm_logits, labels)
        mlm_loss = jnp.sum(mlm_losses * weights) / mlm_denom
        nsp_loss = optax.softmax_cross_entropy_with_integer_labels(
            nsp_logits, nsp).mean() / div
        return mlm_loss + nsp_loss + 0.01 * aux / div

    @jax.jit
    def train_step(params, opt_state, ids, labels, weights, nsp):
        def loss_fn(p):
            loss = batch_loss(p, ids, labels, weights, nsp,
                              jnp.maximum(jnp.sum(weights), 1.0), 1.0)
            with amp.scale_loss(loss, opt_state) as scaled:
                return scaled, loss
        (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state = optimizer.step(params, grads, opt_state)
        return params, opt_state, loss

    accum = args.grad_accum
    if accum < 1:
        raise SystemExit(f"--grad-accum must be >= 1, got {accum}")
    if accum > 1:
        if args.b % accum:
            raise SystemExit(f"batch {args.b} must divide by "
                             f"--grad-accum {accum}")
        if (args.b // accum) % dp:
            raise SystemExit(
                f"microbatch {args.b // accum} (b/{accum}) must divide "
                f"by dp={dp}")

    def make_accum_step(slice_loss_and_grads):
        """Shared grad-accumulation driver (reference delay_unscale /
        unscale_with_stashed protocol), parameterized by the per-slice
        loss-and-grad — GPipe autodiff or the 1F1B schedule: grads
        unscale-accumulated into the stash, the dynamic scale updated
        ONCE per step from the ORed overflow, one optimizer step.  The
        loop unrolls A graphs into the jit — compile time grows with A;
        fine for the usual 2-8.  The accumulated grad equals the
        full-batch grad: the MLM term divides by the GLOBAL mask count.

        ``slice_loss_and_grads(params, st, ids_j, labels_j, weights_j,
        nsp_j, denom) -> (unscaled_loss_contrib, scaled_grads)``.
        """

        @jax.jit
        def train_step(params, opt_state, ids, labels, weights, nsp):
            # STRIDED microbatches (a[j::accum]) keep each microbatch
            # spread across all data-axis devices; a contiguous reshape
            # would land each microbatch on dp/accum devices and force a
            # redistribution every step
            mb = lambda a: jnp.stack([a[j::accum] for j in range(accum)])
            ids_m, labels_m = mb(ids), mb(labels)
            weights_m, nsp_m = mb(weights), mb(nsp)
            denom = jnp.maximum(jnp.sum(weights), 1.0)

            stashed = None
            overflow = jnp.asarray(False)
            st = opt_state
            total_loss = 0.0
            for j in range(accum):
                loss_j, grads = slice_loss_and_grads(
                    params, st, ids_m[j], labels_m[j], weights_m[j],
                    nsp_m[j], denom)
                grads, ovf, st = optimizer.unscale_grads(
                    grads, st, 0, stashed=stashed, update_scale=False)
                stashed = grads
                overflow = overflow | ovf
                total_loss = total_loss + loss_j
            st = optimizer.update_scale(st, overflow, 0)
            params2, st = optimizer.apply_gradients(
                params, stashed, st, overflow)
            return params2, st, total_loss

        return train_step

    if onef1b:
        n_mb = args.pp_microbatches

        def onef1b_slice(params, opt_state, ids_j, labels_j, weights_j,
                         nsp_j, denom, div):
            """One 1F1B pass over a batch slice: the interleaved
            schedule returns scaled grads directly (loss scaling rides
            the per-microbatch loss via ``amp.scale``). The MLM term
            uses the GLOBAL mask count, so each microbatch loss carries
            a ``n_mb * dp`` factor that cancels the schedule's
            mean-over-microbatches and the data-axis pmean; NSP divides
            by ``div`` (the accumulation count)."""

            def mb_loss(mlm_logits, nsp_logits, tgt):
                mlm_losses = \
                    optax.softmax_cross_entropy_with_integer_labels(
                        mlm_logits, tgt["labels"])
                mlm = jnp.sum(mlm_losses * tgt["weights"]) \
                    * (n_mb * dp) / denom
                nsp_loss = \
                    optax.softmax_cross_entropy_with_integer_labels(
                        nsp_logits, tgt["nsp"]).mean() / div
                return amp.scale(mlm + nsp_loss, opt_state)

            targets = {"labels": labels_j, "weights": weights_j,
                       "nsp": nsp_j}
            # the aux joins the objective at the last stage with the
            # same 0.01/div weighting as batch_loss — TIMES the loss
            # scale: the aux never reaches mb_loss, so it must carry
            # the amp scaling itself or optimizer.step's unscale would
            # divide it to nothing
            aux_w = ((0.01 / div) * optimizer.loss_scale(opt_state)
                     if args.moe else 0.0)
            return model.loss_and_grad_1f1b(
                {"params": params}, ids_j, mb_loss, targets,
                moe_aux_weight=aux_w)

        @jax.jit
        def train_step(params, opt_state, ids, labels, weights, nsp):
            """1F1B step: ``optimizer.step`` unscales the schedule's
            grads onto the masters exactly as on the autodiff path."""
            denom = jnp.maximum(jnp.sum(weights), 1.0)
            scale0 = optimizer.loss_scale(opt_state)
            loss_s, grads = onef1b_slice(params, opt_state, ids, labels,
                                         weights, nsp, denom, 1.0)
            params, opt_state = optimizer.step(params, grads, opt_state)
            return params, opt_state, loss_s / scale0

        if accum > 1:
            def onef1b_accum_slice(params, st, ids_j, labels_j,
                                   weights_j, nsp_j, denom):
                # loss_scale(st) is loop-invariant here: the driver
                # defers update_scale to the end of the step
                loss_s, grads = onef1b_slice(
                    params, st, ids_j, labels_j, weights_j, nsp_j,
                    denom, float(accum))
                return loss_s / optimizer.loss_scale(st), grads

            train_step = make_accum_step(onef1b_accum_slice)

    elif accum > 1:
        def gpipe_accum_slice(params, st, ids_j, labels_j, weights_j,
                              nsp_j, denom):
            def loss_fn(p):
                loss = batch_loss(p, ids_j, labels_j, weights_j, nsp_j,
                                  denom, float(accum))
                with amp.scale_loss(loss, st) as scaled:
                    return scaled, loss
            (_, loss_j), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            return loss_j, grads

        train_step = make_accum_step(gpipe_accum_slice)

    rng = np.random.RandomState(0)
    losses, batch_time = AverageMeter(), AverageMeter()
    end = time.time()
    for i in range(args.steps):
        ids, labels, weights, nsp = synthetic_mlm_batch(rng, args, cfg)
        batch = [jax.device_put(jnp.asarray(a), shard)
                 for a in (ids, labels, weights, nsp)]
        params, opt_state, loss = train_step(params, opt_state, *batch)
        if i % args.print_freq == 0:
            losses.update(float(loss))
            # the interval spans print_freq steps (1 for the compile step)
            batch_time.update((time.time() - end) / (args.print_freq
                                                     if i else 1))
            seq_per_s = args.b / batch_time.val if batch_time.val else 0.0
            maybe_print(
                f"step {i}/{args.steps}  Loss {losses.val:.4f} "
                f"({losses.avg:.4f})  Speed {seq_per_s:.1f} seq/s  "
                f"scale {float(optimizer.loss_scale(opt_state)):.0f}",
                rank0=True)
            end = time.time()


if __name__ == "__main__":
    main()
