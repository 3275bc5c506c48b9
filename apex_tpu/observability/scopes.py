"""One vocabulary of names for the blocks of the device programs.

Every compiled program of the package (the serving engine's decode,
verify and chunk programs and their sampled twins, the amp training
step) names the block each of its operations belongs to with
:func:`device_scope`, a ``jax.named_scope`` restricted to
:data:`DEVICE_SCOPES`.  A named scope is operation metadata only: the
compiled instructions are the same with or without it, so the names
cost nothing at run time.  They reach the profiler as each XLA
operation's ``tf_op`` path (``jit(_decode_stoch_impl)/GPTLMHeadModel/
block_3/attention/kv_write/scatter:``), which xprof and Perfetto show
beside the operation.

Scopes nest as the code nests.  A reader takes the innermost name of
the vocabulary on an operation's path and ignores the rest of it
(flax module names, ``jit(...)``, and the wrappers a transformation
puts round a name: ``transpose(jvp(attention))`` is ``attention``), so
the backward pass lands in the block of its forward operation.
jax is resolved on first use; this module imports without it.
"""

DEVICE_SCOPES = (
    "embed",            # token and position embedding lookups
    "norm",             # layer and RMS norms outside the head
    "attention",        # every attention kind, the pool reads within
    "short_conv",       # the convolution mixer of the lfm2 family
    "mlp",              # dense feed-forward layers
    "moe_router",       # expert scores and the choice of experts
    "moe_experts",      # the routed experts' grouped products
    "moe_shared",       # the shared experts beside them
    "kv_write",         # pool writes and the slot arithmetic for them
    "head",             # final norm, vocabulary product, loss or take
    "sample",           # argmax, the finite guard, the sampler
    "optimizer",        # amp's unscale and the optimizer's update
    "grad_exchange",    # the data-parallel gradient all-reduce
)


def device_scope(name):
    """``jax.named_scope(name)`` for a name of :data:`DEVICE_SCOPES`;
    any other name is a ``ValueError``, so that a reader of the trace
    never meets a block it does not know."""
    if name not in DEVICE_SCOPES:
        raise ValueError(f"{name!r} is no device scope; the vocabulary is "
                         f"{DEVICE_SCOPES}")
    import jax
    return jax.named_scope(name)
