"""The weights, made on the device from the seed in one jitted call.

``table`` is ``{path: (shape, kind)}`` as the configuration's
reference gives it.  Leaves that differ only in their block index are
drawn as one stacked array and sliced, so that the program has a few
dozen random draws to compile, not several hundred.
"""

import re

import jax
import jax.numpy as jnp

_BLOCK = re.compile(r"^block_(\d+)/(.*)$")


def seed_key(seed):
    """A key from any whole number: seeds run past 32 signed bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 31)


def _nest(flat):
    out = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def make_params(table, seed, dtype, std, sharding=None):
    groups = {}                     # (suffix or path, shape, kind) -> [paths]
    for path, (shape, kind) in sorted(table.items()):
        m = _BLOCK.match(path)
        groups.setdefault((m.group(2) if m else path, tuple(shape), kind),
                          []).append(path)

    def make(key):
        flat = {}
        for i, ((_, shape, kind), paths) in enumerate(sorted(groups.items())):
            full = (len(paths),) + shape
            if kind == "normal":
                block = (jax.random.normal(jax.random.fold_in(key, i), full,
                                           jnp.float32) * std).astype(dtype)
            elif kind in ("zeros", "ones"):
                block = jnp.full(full, float(kind == "ones"), dtype)
            else:
                raise ValueError(f"unknown initializer {kind!r}")
            for j, path in enumerate(paths):
                flat[path] = block[j]
        return _nest(flat)

    return jax.jit(make, out_shardings=sharding)(seed_key(seed))
