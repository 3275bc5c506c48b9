"""GPT-2 as published, in plain ``jax.numpy``: the yardstick the timed
path is compared with.

Radford et al. 2019 ("Language Models are Unsupervised Multitask
Learners") on top of Vaswani et al. 2017: learned token and position
embeddings, ``n_layer`` pre-LayerNorm blocks (``x + Attn(LN(x))``,
``x + MLP(LN(x))``), causal multi-head attention with scores scaled by
``1/sqrt(head_dim)``, a 4x MLP with the tanh-approximated GELU, a final
LayerNorm, and the output head tied to the token embedding.  Training
adds the mean next-token cross entropy and Adam (Kingma & Ba 2015) in the
order of computation its section 2 gives for efficiency, which is the
one NVIDIA apex's FusedAdam (and so this library's) implements:
``lr_t = lr * sqrt(1 - b2^t) / (1 - b1^t)`` and ``p -= lr_t * m /
(sqrt(v) + eps_hat)``, with the configuration's ``eps`` as ``eps_hat``.  No kernels, no cache, no batching tricks, and nothing
imported from the program under test.

Parameters arrive as the nested dictionary the benchmark makes from the
seed (``harness/weights.py``), under the names ``param_table`` gives.

``precision`` selects the arithmetic.  ``"float32"`` is the reference:
float32 everywhere, matrix products at ``Precision.HIGHEST``.  The
others are the controls of ``compare.py``: ``"bfloat16"`` rounds the
operands of every matrix product to bfloat16, ``"fp8"`` to float8
(e4m3, scaled per tensor by its largest magnitude, as fp8 recipes do),
in the forward pass; gradients flow back unrounded.
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("float32", "bfloat16", "fp8")


def vocab(sizes):
    """How many ids there are: the traffic draws from ``range(vocab)``."""
    return sizes["vocab_size"]


def longest_row(sizes):
    """The longest row of ids ``logits`` takes: the learned positions."""
    return sizes["n_positions"]


def weight_std(sizes):
    """The standard deviation of ``param_table``'s ``normal`` leaves."""
    return sizes["initializer_range"]


def param_table(sizes):
    """``{path: (shape, kind)}`` for every parameter, kind one of
    ``normal`` (N(0, ``weight_std``)), ``zeros``, ``ones``."""
    h, nh = sizes["n_embd"], sizes["n_head"]
    hd, inner = h // nh, sizes["n_inner"] or 4 * sizes["n_embd"]
    table = {"wte/embedding": ((sizes["vocab_size"], h), "normal"),
             "wpe/embedding": ((sizes["n_positions"], h), "normal"),
             "final_ln/scale": ((h,), "ones"),
             "final_ln/bias": ((h,), "zeros")}
    for i in range(sizes["n_layer"]):
        b = f"block_{i}/"
        for name in ("query", "key", "value"):
            table[b + f"attention/{name}/kernel"] = ((h, nh, hd), "normal")
            table[b + f"attention/{name}/bias"] = ((nh, hd), "zeros")
        table[b + "attention/output/kernel"] = ((nh, hd, h), "normal")
        table[b + "attention/output/bias"] = ((h,), "zeros")
        for ln in ("attn_ln", "mlp_ln"):
            table[b + ln + "/scale"] = ((h,), "ones")
            table[b + ln + "/bias"] = ((h,), "zeros")
        table[b + "mlp_in/kernel"] = ((h, inner), "normal")
        table[b + "mlp_in/bias"] = ((inner,), "zeros")
        table[b + "mlp_out/kernel"] = ((inner, h), "normal")
        table[b + "mlp_out/bias"] = ((h,), "zeros")
    return table


def _rounder(precision):
    """What a matrix product's operands are rounded to."""
    if precision == "float32":
        return lambda x: x
    # lax.reduce_precision, not a cast there and back: XLA may elide a
    # pair of casts, and then the control computes in float32
    if precision == "bfloat16":
        def low(x):
            return jax.lax.reduce_precision(x, exponent_bits=8,
                                            mantissa_bits=7)
    elif precision == "fp8":
        def low(x):
            # e4m3's largest finite value is 240 in IEEE form
            scale = 224.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
            return jax.lax.reduce_precision(
                x * scale, exponent_bits=4, mantissa_bits=3) / scale
    else:
        raise ValueError(
            f"precision {precision!r} is not one of {PRECISIONS}")
    # rounded going forward, untouched going back: a cotangent sent
    # through an unscaled float8 cast would underflow to nought, which
    # is a fault of the cast and not what lower precision costs
    return lambda x: x + jax.lax.stop_gradient(low(x) - x)


def _mm(spec, a, b, q):
    return jnp.einsum(spec, q(a), q(b), precision=HIGHEST)


def layer_norm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(x, p, eps, q):
    """One pre-LN block on ``x`` (rows, T, h), float32."""
    t = x.shape[1]
    a = p["attention"]
    y = layer_norm(x, p["attn_ln"], eps)
    qh = _mm("bth,hnd->btnd", y, a["query"]["kernel"], q) + a["query"]["bias"]
    kh = _mm("bth,hnd->btnd", y, a["key"]["kernel"], q) + a["key"]["bias"]
    vh = _mm("bth,hnd->btnd", y, a["value"]["kernel"], q) + a["value"]["bias"]
    s = _mm("bqnd,bknd->bnqk", qh, kh, q) / jnp.sqrt(
        jnp.float32(qh.shape[-1]))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    ctx = _mm("bnqk,bknd->bqnd", w, vh, q)
    x = x + _mm("bqnd,ndh->bqh", ctx, a["output"]["kernel"], q) \
        + a["output"]["bias"]
    y = layer_norm(x, p["mlp_ln"], eps)
    y = gelu_tanh(_mm("bth,hi->bti", y, p["mlp_in"]["kernel"], q)
                  + p["mlp_in"]["bias"])
    return x + _mm("bti,ih->bth", y, p["mlp_out"]["kernel"], q) \
        + p["mlp_out"]["bias"]


def stack_blocks(params, n_layer):
    """The blocks' parameters stacked along a leading layer axis, so
    that one ``lax.scan`` body serves every layer."""
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[params[f"block_{i}"] for i in range(n_layer)])


def logits(params, ids, sizes, precision="float32", remat=False):
    """``ids`` (rows, T) -> float32 logits (rows, T, vocab).  Weights of
    any float type are widened to float32 one layer at a time."""
    q = _rounder(precision)
    eps = sizes["layer_norm_epsilon"]
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    wte = params["wte"]["embedding"].astype(jnp.float32)
    x = wte[ids] + params["wpe"]["embedding"].astype(
        jnp.float32)[: ids.shape[1]][None]

    def body(x, p):
        return block(x, f32(p), eps, q), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = layer_norm(x, f32(params["final_ln"]), eps)
    return _mm("bth,vh->btv", x, wte, q)


def next_token_loss(params, ids, sizes, precision="float32"):
    """Mean cross entropy of token t+1 given tokens <= t."""
    lg = logits(params, ids, sizes, precision, remat=True)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]
    return -jnp.mean(picked)


def stacked(params, sizes):
    """The parameter tree with its blocks stacked (``"blocks"``)."""
    out = {k: v for k, v in params.items() if not k.startswith("block_")}
    out["blocks"] = stack_blocks(params, sizes["n_layer"])
    return out


def unstacked_leaf_norms(tree, sizes):
    """Per-leaf L2 norms keyed by the unstacked path (``block_3/...``),
    from a tree whose blocks are stacked."""
    norms = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [k.key for k in path]
        if keys[0] == "blocks":
            per = jnp.sqrt(jnp.sum(
                jnp.square(leaf.astype(jnp.float32)).reshape(
                    leaf.shape[0], -1), -1))
            for i in range(sizes["n_layer"]):
                norms["/".join([f"block_{i}"] + keys[1:])] = per[i]
        else:
            norms["/".join(keys)] = jnp.sqrt(jnp.sum(jnp.square(
                leaf.astype(jnp.float32))))
    return norms


def train_steps(params, batches, sizes, hyper, precision="float32",
                rows_per_block=2):
    """Follow ``len(batches)`` Adam steps from ``params`` (float32,
    blocks stacked).  Each batch (rows, T) is taken in blocks of
    ``rows_per_block`` rows whose gradients are summed, so that the
    activations fit beside the state; every row has the same number of
    targets, so the mean of the blocks' means is the batch's mean.

    Returns the steps' losses, the first step's gradient and the
    parameters after the last step."""
    b1, b2 = hyper["betas"]
    lr, eps = hyper["lr"], hyper["eps"]

    @jax.jit
    def block_grad(params, ids):
        return jax.value_and_grad(next_token_loss)(
            params, ids, sizes, precision)

    @jax.jit
    def add(acc, new):
        return jax.tree.map(jnp.add, acc, new)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam(params, m, v, grads, t):
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
        lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        params = jax.tree.map(
            lambda p, m, v: p - lr_t * m / (jnp.sqrt(v) + eps),
            params, m, v)
        return params, m, v

    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    params = jax.tree.map(jnp.copy, params)
    losses, first_grad = [], None
    for t, ids in enumerate(batches, start=1):
        n_blocks = ids.shape[0] // rows_per_block
        if n_blocks * rows_per_block != ids.shape[0]:
            raise ValueError(f"{ids.shape[0]} rows do not divide into "
                             f"blocks of {rows_per_block}")
        loss, grads = 0.0, None
        for i in range(n_blocks):
            rows = ids[i * rows_per_block:(i + 1) * rows_per_block]
            l, g = block_grad(params, rows)
            loss = loss + l
            grads = g if grads is None else add(grads, g)
        grads = jax.tree.map(lambda g: g / n_blocks, grads)
        losses.append(loss / n_blocks)
        if first_grad is None:
            first_grad = grads
        params, m, v = adam(params, m, v, grads, jnp.float32(t))
    return losses, first_grad, params


def token_gaps(params, ids, lengths, sizes, precision="float32"):
    """For rows of prompt-plus-output ``ids`` (rows, T) with true
    ``lengths``: the logits at every position, reduced to what the
    comparison needs.  Returns ``(best, gap_of_next, argmax)``, each
    (rows, T-1): the largest logit at position t, how far the logit of
    the token that really follows (``ids[t+1]``) lies below it, and the
    token this precision puts first.  Positions at or past ``length-1``
    are padding; the caller masks them."""
    lg = logits(params, ids, sizes, precision)[:, :-1]
    best = jnp.max(lg, -1)
    nxt = jnp.take_along_axis(lg, ids[:, 1:, None], -1)[..., 0]
    return best, best - nxt, jnp.argmax(lg, -1)


def mass_above(params, ids, sizes, temperature, precision="float32"):
    """At every position t (rows, T-1): the probability, at
    ``temperature``, of all the tokens whose logit exceeds that of the
    token that really follows (``ids[t+1]``).  Nucleus sampling keeps
    the smallest set of most probable tokens whose mass reaches
    ``top_p``, the token that crosses it included: a token is kept
    exactly where the mass above it is under ``top_p``."""
    lg = logits(params, ids, sizes, precision)[:, :-1]
    nxt = jnp.take_along_axis(lg, ids[:, 1:, None], -1)
    p = jax.nn.softmax(lg / temperature, axis=-1)
    return jnp.sum(jnp.where(lg > nxt, p, 0.0), -1)


def logit_at(params, ids, tokens, sizes, precision="float32"):
    """Float32-reference logits of chosen ``tokens`` (rows, T-1) at each
    position, beside the position's best: how far the token that a
    lower precision put first lies below the reference's best."""
    lg = logits(params, ids, sizes, precision)[:, :-1]
    return jnp.max(lg, -1) - jnp.take_along_axis(
        lg, tokens[..., None], -1)[..., 0]
