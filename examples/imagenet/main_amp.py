"""ImageNet training with amp + DDP + SyncBN — the flagship workload.

TPU-native port of the reference's ``examples/imagenet/main_amp.py``
(CLI flags at reference :40-110, train loop :306-372): ResNet under
mixed precision, data-parallel over every available chip, optional
synchronized BatchNorm, rank0-aware printing of Loss / Speed / Prec@1,5.

Design differences from the reference (by construction, not omission):

- Distribution is GSPMD: ONE process jits the train step over a
  ``jax.sharding.Mesh`` covering all chips; the batch is sharded on the
  ``data`` axis and params are replicated. The gradient all-reduce the
  reference gets from DDP hooks (``apex/parallel/distributed.py:291-372``)
  falls out of the loss-mean math; apex numeric policy
  (``allreduce_always_fp32`` etc.) is available via
  ``parallel.DistributedDataParallel`` for shard_map users.
- ``--sync_bn`` swaps the model's norm factory for
  ``parallel.SyncBatchNorm`` (the flax analog of
  ``convert_syncbn_model``, reference ``parallel/__init__.py:21-53``).
  Under GSPMD, batch statistics are global by construction, which IS
  SyncBN semantics.
- The input pipeline is synthetic by default (no dataset download in CI);
  ``--data DIR`` expects ``.npz`` shards with ``x``(NHWC uint8)/``y``.
  The reference's DALI/torchvision loaders are replaced by a host-side
  prefetching iterator (apex_tpu.data).
- ``--prof N`` wraps N iterations in ``jax.profiler`` trace annotations
  (the reference uses nvtx push/pop, :311-334).
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu import amp, models, parallel
from apex_tpu.data import prefetch_to_device, put_global
from apex_tpu.utils import AverageMeter, maybe_print
from apex_tpu.utils.compile_cache import enable_compile_cache


ARCHS = {
    "resnet18": models.ResNet18, "resnet34": models.ResNet34,
    "resnet50": models.ResNet50, "resnet101": models.ResNet101,
    "resnet152": models.ResNet152,
}


def parse_args():
    p = argparse.ArgumentParser(
        description="ImageNet training with apex_tpu amp (TPU)")
    p.add_argument("--data", default=None,
                   help="dataset dir: either torchvision-ImageFolder layout "
                   "(train/<class>/*.jpg [+ val/<class>/*.jpg]) or .npz "
                   "shards (x: NHWC uint8, y: int); synthetic when omitted")
    p.add_argument("--arch", "-a", default="resnet50", choices=sorted(ARCHS))
    p.add_argument("--stem", default="conv", choices=["conv", "s2d"],
                   help="s2d = space-to-depth stem (MLPerf TPU layout; "
                   "exactly equivalent math, MXU-friendlier 4x4x12 "
                   "kernel; --torch-weights converts automatically)")
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--b", "--batch-size", type=int, default=256, dest="b",
                   help="PER-HOST batch size (split over this host's "
                   "chips; global batch = b * process_count, the "
                   "reference's per-rank convention)")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--warmup-epochs", type=int, default=5,
                   help="linear lr warmup epochs (reference "
                   "adjust_learning_rate, main_amp.py:464-500)")
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--steps-per-epoch", type=int, default=100,
                   help="epoch length for synthetic/npz data (ImageFolder "
                   "derives it from the dataset size)")
    p.add_argument("--val-steps", type=int, default=10,
                   help="validation batches for synthetic data")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--workers", type=int, default=8,
                   help="decode threads for the ImageFolder loader")
    p.add_argument("--deterministic", action="store_true")
    p.add_argument("--evaluate", action="store_true",
                   help="validate and exit (reference --evaluate)")
    p.add_argument("--prof", type=int, default=None,
                   help="profile N iterations then exit")
    p.add_argument("--sync_bn", action="store_true",
                   help="use apex_tpu.parallel.SyncBatchNorm")
    # amp flags: strings pass straight through like the reference CLI
    # (reference main_amp.py:71-73 takes strings so None/dynamic work)
    p.add_argument("--opt-level", default="O2",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--keep-batchnorm-fp32", default=None)
    p.add_argument("--loss-scale", default=None)
    p.add_argument("--resume", default=None,
                   help="checkpoint dir to resume from")
    p.add_argument("--checkpoint-dir", default=None,
                   help="save last/best checkpoints when set")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO-1: shard optimizer state across the data "
                   "axis (parallel.shard_optimizer_state)")
    p.add_argument("--torch-weights", default=None, metavar="PT",
                   help="initialize from a torchvision-format torch "
                   "checkpoint (.pt state_dict; 'module.' DDP prefixes "
                   "handled) via utils.load_torch_resnet")
    return p.parse_args()


def synthetic_batches(args, steps, seed=0):
    """Host-side synthetic NHWC uint8 batches, matching the reference's
    image pipeline output (pixels; normalization runs on device)."""
    rng = np.random.RandomState(seed)
    while True:
        for _ in range(steps):
            x = rng.randint(
                0, 256, (args.b, args.image_size, args.image_size, 3),
                dtype=np.uint8)
            y = rng.randint(0, args.num_classes, (args.b,), dtype=np.int32)
            yield x, y


def npz_batches(args, steps):
    from apex_tpu.data import npz_loader
    return npz_loader(args.data, batch_size=args.b, steps_per_epoch=steps,
                      num_shards=jax.process_count(),
                      shard_index=jax.process_index())


def make_loaders(args):
    """Route --data to the right pipeline; returns
    (train_iter, make_val_iter | None, steps_per_epoch)."""
    import glob as _glob
    import os as _os

    if args.data is None:
        train = synthetic_batches(args, args.steps_per_epoch)
        # fixed-seed synthetic val set so --evaluate works hermetically
        make_val = lambda: iter(
            [b for _, b in zip(range(args.val_steps),
                               synthetic_batches(args, args.val_steps,
                                                 seed=1234))])
        return train, make_val, args.steps_per_epoch

    train_dir = _os.path.join(args.data, "train")
    if _os.path.isdir(train_dir):  # ImageFolder layout (reference default)
        import jax as _jax

        from apex_tpu.data import image_folder_loader
        from apex_tpu.data.loaders import _list_image_folder

        # multi-host: each process loads its disjoint sample shard
        # (the reference's DistributedSampler); args.b is the PER-HOST
        # batch and put_global assembles the process-local batches into
        # the (process_count * b)-row global array
        nsh, sh = _jax.process_count(), _jax.process_index()
        train_samples = _list_image_folder(train_dir)[0]  # one scan
        steps = max(1, len(train_samples) // nsh // args.b)
        train = image_folder_loader(
            train_dir, args.b, image_size=args.image_size, train=True,
            num_workers=args.workers, samples=train_samples,
            num_shards=nsh, shard_index=sh)
        val_dir = _os.path.join(args.data, "val")
        make_val = None
        if _os.path.isdir(val_dir):
            make_val = lambda: image_folder_loader(
                val_dir, args.b, image_size=args.image_size, train=False,
                num_workers=args.workers, loop=False,
                num_shards=nsh, shard_index=sh)
        return train, make_val, steps
    if _glob.glob(_os.path.join(args.data, "*.npz")):
        return (npz_batches(args, args.steps_per_epoch), None,
                args.steps_per_epoch)
    raise SystemExit(f"--data {args.data}: neither train/ subdir nor .npz "
                     "shards found")


def lr_schedule(args, steps_per_epoch):
    """The reference's schedule (``adjust_learning_rate``,
    ``main_amp.py:464-500``): linear warmup over the first
    ``--warmup-epochs``, then step decay x0.1 at ABSOLUTE epochs
    30/60/80."""
    import optax
    warmup = args.warmup_epochs * steps_per_epoch
    # join_schedules rebases the second schedule's step count to the
    # boundary, so express the absolute-epoch decay points relative to
    # the end of warmup
    decay = optax.piecewise_constant_schedule(
        args.lr, {max(e * steps_per_epoch - warmup, 1): 0.1
                  for e in (30, 60, 80)})
    if warmup == 0:
        return decay
    return optax.join_schedules(
        [optax.linear_schedule(args.lr / max(warmup, 1), args.lr, warmup),
         decay], [warmup])


MEAN = np.array([0.485, 0.456, 0.406], np.float32) * 255.0
STD = np.array([0.229, 0.224, 0.225], np.float32) * 255.0


def main():
    args = parse_args()
    enable_compile_cache()
    if args.deterministic:
        jax.config.update("jax_default_matmul_precision", "highest")

    devices = jax.devices()
    n_dev = len(devices)
    if args.b % n_dev != 0:
        raise SystemExit(f"global batch {args.b} must divide by {n_dev} chips")
    mesh = Mesh(np.array(devices), axis_names=("data",))
    maybe_print(f"devices: {n_dev} x {devices[0].platform}", rank0=True)

    norm = (parallel.SyncBatchNorm if args.sync_bn
            else models.resnet.default_norm)
    model = ARCHS[args.arch](num_classes=args.num_classes, norm=norm,
                             stem=args.stem)

    batches, make_val, steps_per_epoch = make_loaders(args)

    tx = optax.sgd(lr_schedule(args, steps_per_epoch),
                   momentum=args.momentum)
    if args.weight_decay:
        tx = optax.chain(optax.add_decayed_weights(args.weight_decay), tx)

    model, optimizer = amp.initialize(
        model, tx, opt_level=args.opt_level,
        keep_batchnorm_fp32=args.keep_batchnorm_fp32,
        loss_scale=args.loss_scale)

    rng = jax.random.PRNGKey(0)
    dummy = jnp.ones((1, args.image_size, args.image_size, 3), jnp.float32)
    variables = model.init(rng, dummy, train=True)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    if args.torch_weights:
        # migration path: start from a torchvision-format checkpoint
        # (e.g. one trained with the reference library)
        import torch
        from apex_tpu.utils import load_torch_resnet
        sd = torch.load(args.torch_weights, map_location="cpu")
        sd = sd.get("state_dict", sd)  # accept full checkpoint dicts
        converted = load_torch_resnet(
            sd, arch=args.arch,
            norm_name="SyncBatchNorm" if args.sync_bn else "BatchNorm",
            stem=args.stem)
        # amp owns the canonical dtype layout (fp32 masters / O3 half,
        # batch_stats included)
        converted = model.canonical_variables(converted)
        params, batch_stats = (converted["params"],
                               converted["batch_stats"])
        maybe_print(f"loaded torch weights from {args.torch_weights}",
                    rank0=True)
    opt_state = optimizer.init(params)

    start_epoch = 0
    best_prec1 = 0.0
    if args.resume:
        from apex_tpu.utils import checkpoint as ckpt
        state = ckpt.restore(args.resume, {
            "params": params, "batch_stats": batch_stats,
            "opt_state": opt_state, "epoch": 0, "best_prec1": 0.0})
        params, batch_stats = state["params"], state["batch_stats"]
        opt_state, start_epoch = state["opt_state"], int(state["epoch"]) + 1
        best_prec1 = float(state.get("best_prec1", 0.0))
        maybe_print(f"resumed from {args.resume} at epoch {start_epoch} "
                    f"(best prec@1 {best_prec1:.2f})", rank0=True)

    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("data"))
    params = jax.device_put(params, repl)
    batch_stats = jax.device_put(batch_stats, repl)
    if args.zero:
        # moments shard over the data axis; GSPMD runs the optimizer
        # update shard-local (pair with a non-Pallas optimizer step —
        # docs/parallel.md)
        opt_state = parallel.shard_optimizer_state(opt_state, mesh)
    else:
        opt_state = jax.device_put(opt_state, repl)
    mean = jnp.asarray(MEAN)
    std = jnp.asarray(STD)

    import functools
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, batch_stats, opt_state, x, y):
        x = (x.astype(jnp.float32) - mean) / std

        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": batch_stats}, x, train=True,
                mutable=["batch_stats"])
            logits = logits.astype(jnp.float32)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            with amp.scale_loss(loss, opt_state) as scaled:
                return scaled, (loss, logits, updates["batch_stats"])
        grads, (loss, logits, new_stats) = jax.grad(
            loss_fn, has_aux=True)(params)
        params, opt_state = optimizer.step(params, grads, opt_state)
        top5 = jnp.argsort(logits, axis=-1)[:, -5:]
        prec1 = jnp.mean((top5[:, -1] == y).astype(jnp.float32)) * 100
        prec5 = jnp.mean(jnp.any(top5 == y[:, None], axis=1)
                         .astype(jnp.float32)) * 100
        return params, new_stats, opt_state, loss, prec1, prec5

    @jax.jit
    def eval_step(params, batch_stats, x, y):
        x = (x.astype(jnp.float32) - mean) / std
        logits = model.apply(
            {"params": params, "batch_stats": batch_stats}, x,
            train=False).astype(jnp.float32)
        top5 = jnp.argsort(logits, axis=-1)[:, -5:]
        # GLOBAL scalar sums over valid (non-padding, y >= 0) rows:
        # replicated outputs every host can read — per-example vectors
        # would span non-addressable devices on multi-host (the
        # reference all-reduces val metrics the same way,
        # reduce_tensor, main_amp.py:499-503)
        valid = y >= 0
        c1 = jnp.sum((top5[:, -1] == y) & valid)
        c5 = jnp.sum(jnp.any(top5 == y[:, None], axis=1) & valid)
        return c1, c5, jnp.sum(valid)

    def validate(params, batch_stats):
        """Full prec@1/5 over the val set (reference ``validate()``,
        ``main_amp.py:376-443``); pads ragged final batches to keep the
        jit shape static and the batch divisible over chips."""
        if make_val is None:
            return None, None
        n = c1 = c5 = 0
        end = time.time()
        batch_time = AverageMeter()
        for x, y in make_val():
            bs = x.shape[0]
            if bs < args.b:  # pad final batch to the static step shape
                pad = args.b - bs
                x = np.concatenate([x, np.zeros((pad,) + x.shape[1:],
                                                x.dtype)])
                y = np.concatenate([y, np.full((pad,), -1, y.dtype)])
            xd = put_global(x, shard)
            yd = put_global(y, shard)
            c1v, c5v, nv = eval_step(params, batch_stats, xd, yd)
            c1 += int(c1v)   # replicated global scalars: same on every
            c5 += int(c5v)   # host, so best-checkpoint choices agree
            n += int(nv)
            batch_time.update(time.time() - end)
            end = time.time()
        if n == 0:  # e.g. a val set smaller than the shard count
            maybe_print("validate: no validation batches on this shard; "
                        "skipping metrics", rank0=True)
            return None, None
        prec1, prec5 = 100.0 * c1 / n, 100.0 * c5 / n
        maybe_print(f" * Prec@1 {prec1:.3f} Prec@5 {prec5:.3f} "
                    f"({n} images, {batch_time.avg:.3f}s/batch)",
                    rank0=True)
        return prec1, prec5

    if args.evaluate:
        if make_val is None:
            raise SystemExit(
                "--evaluate needs a validation source: an ImageFolder "
                "--data dir with a val/ subdir, or synthetic data (no "
                "--data)")
        validate(params, batch_stats)
        return

    if args.prof:
        profile(args, train_step, params, batch_stats, opt_state, batches,
                shard)
        return

    # background-thread host->device staging, one batch ahead: the copy
    # overlaps the previous step's compute (the pinned-memory /
    # non_blocking analog; reference uses DataLoader workers + CUDA
    # streams for the same overlap)
    batches_dev = prefetch_to_device(batches, size=2, sharding=shard)

    for epoch in range(start_epoch, args.epochs):
        batch_time, losses, top1, top5m = (AverageMeter() for _ in range(4))
        end = time.time()
        for i in range(steps_per_epoch):
            x, y = next(batches_dev)
            params, batch_stats, opt_state, loss, p1, p5 = train_step(
                params, batch_stats, opt_state, x, y)
            if i % args.print_freq == 0:
                # sync point only at print frequency (the reference also
                # syncs per print via .item(), main_amp.py:336-372)
                loss = float(loss)
                batch_time.update((time.time() - end) / args.print_freq
                                  if i else time.time() - end)
                losses.update(loss, args.b)
                top1.update(float(p1), args.b)
                top5m.update(float(p5), args.b)
                speed = args.b / batch_time.val if batch_time.val else 0.0
                maybe_print(
                    f"Epoch: [{epoch}][{i}/{steps_per_epoch}]\t"
                    f"Time {batch_time.val:.3f} ({batch_time.avg:.3f})\t"
                    f"Speed {speed:.1f}\t"
                    f"Loss {losses.val:.4f} ({losses.avg:.4f})\t"
                    f"Prec@1 {top1.val:.2f} ({top1.avg:.2f})\t"
                    f"Prec@5 {top5m.val:.2f} ({top5m.avg:.2f})",
                    rank0=True)
                end = time.time()

        prec1, _ = validate(params, batch_stats)

        if args.checkpoint_dir:
            import os as _os
            from apex_tpu.utils import checkpoint as ckpt
            is_best = prec1 is not None and prec1 > best_prec1
            if is_best:
                best_prec1 = prec1
            save_opt = (parallel.unshard_optimizer_state(opt_state, mesh)
                        if args.zero else opt_state)
            state = {"params": params, "batch_stats": batch_stats,
                     "opt_state": save_opt, "epoch": epoch,
                     "best_prec1": best_prec1}
            ckpt.save(_os.path.join(args.checkpoint_dir, "last"), state)
            if is_best:  # reference's shutil.copyfile best-model pattern
                ckpt.save(_os.path.join(args.checkpoint_dir, "best"), state)
            maybe_print(
                f"saved checkpoint for epoch {epoch}"
                + (f" (new best prec@1 {best_prec1:.2f})" if is_best else ""),
                rank0=True)


def profile(args, train_step, params, batch_stats, opt_state, batches, shard):
    """--prof short-run mode: the reference wraps N iterations in nvtx
    ranges (main_amp.py:311-334); here each phase gets a TraceAnnotation
    and the run exits after N steps."""
    from apex_tpu.utils import trace_annotation
    for i in range(args.prof):
        x, y = next(batches)
        with trace_annotation(f"iter_{i}"):
            x = put_global(x, shard)
            y = put_global(y, shard)
            params, batch_stats, opt_state, loss, _, _ = train_step(
                params, batch_stats, opt_state, x, y)
        jax.block_until_ready(loss)
    maybe_print(f"profiled {args.prof} iterations; loss={float(loss):.4f}",
                rank0=True)


if __name__ == "__main__":
    main()
