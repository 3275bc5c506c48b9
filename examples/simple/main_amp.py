"""Minimal amp example: MLP classifier with O0-O3 optimization levels.

TPU-native port of the reference's minimal usage pattern
(``examples/simple/distributed/distributed_data_parallel.py`` and the amp
snippet in ``README.md``): build a model, ``amp.initialize`` it, train with
the ``scale_loss`` protocol. Runs on CPU or a single TPU chip.

Data is synthetic (gaussian clusters) by default so the example runs with
zero downloads; pass --mnist-npz PATH to use a local MNIST .npz instead.
"""

import argparse
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from apex_tpu import amp
from apex_tpu.utils.compile_cache import enable_compile_cache


class MLP(nn.Module):
    hidden: int = 256
    n_classes: int = 10

    @nn.compact
    def __call__(self, x):
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.hidden)(x)
        x = nn.relu(x)
        x = nn.Dense(self.hidden)(x)
        x = nn.relu(x)
        return nn.Dense(self.n_classes)(x)


def synthetic_data(n, d, n_classes, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.randn(n_classes, d) * 3
    y = rng.randint(0, n_classes, n)
    x = centers[y] + rng.randn(n, d)
    return x.astype(np.float32), y.astype(np.int32)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--opt-level", default="O1",
                        choices=["O0", "O1", "O2", "O3"])
    parser.add_argument("--loss-scale", default=None,
                        help="'dynamic' or a float (string, passed through "
                        "like the reference examples)")
    parser.add_argument("--epochs", type=int, default=5)
    parser.add_argument("--batch-size", type=int, default=256)
    parser.add_argument("--lr", type=float, default=0.05)
    parser.add_argument("--mnist-npz", default=None)
    args = parser.parse_args()
    enable_compile_cache()

    if args.mnist_npz:
        with np.load(args.mnist_npz) as z:
            x_train, y_train = z["x_train"].astype(np.float32) / 255.0, \
                z["y_train"].astype(np.int32)
        d = int(np.prod(x_train.shape[1:]))
        x_train = x_train.reshape(-1, d)
    else:
        x_train, y_train = synthetic_data(8192, 784, 10)
        d = 784

    model, optimizer = amp.initialize(
        MLP(), optax.sgd(args.lr), opt_level=args.opt_level,
        loss_scale=args.loss_scale)
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, d)))
    opt_state = optimizer.init(params)

    @jax.jit
    def train_step(params, opt_state, x, y):
        def loss_fn(p):
            logits = model.apply(p, x).astype(jnp.float32)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            with amp.scale_loss(loss, opt_state) as scaled_loss:
                return scaled_loss, loss
        (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state = optimizer.step(params, grads, opt_state)
        return params, opt_state, loss

    n = x_train.shape[0]
    steps_per_epoch = n // args.batch_size
    for epoch in range(args.epochs):
        t0 = time.time()
        perm = np.random.RandomState(epoch).permutation(n)
        epoch_loss = 0.0
        for i in range(steps_per_epoch):
            idx = perm[i * args.batch_size:(i + 1) * args.batch_size]
            params, opt_state, loss = train_step(
                params, opt_state, jnp.asarray(x_train[idx]),
                jnp.asarray(y_train[idx]))
            epoch_loss += float(loss)
        dt = time.time() - t0
        speed = steps_per_epoch * args.batch_size / dt
        print(f"Epoch {epoch}: loss {epoch_loss / steps_per_epoch:.4f}  "
              f"Speed {speed:.1f} samples/s  "
              f"loss_scale {float(optimizer.loss_scale(opt_state)):.0f}")


if __name__ == "__main__":
    main()
