"""``FusedAdam().step`` compiled for a described v5e by the real Mosaic
and XLA:TPU compilers, with no chip: what "the flat step moves its
buffer as few times as it must" promises is read off the compiled text.

Over a GPT-2-shaped tree (the medium widths, 2 layers, the whole
vocabulary; the state and the parameters donated) the entry computation
holds, of ``spec.total`` elements or more, nothing but the program's
parameters, the two gathers (parameters and gradients into one buffer
each), the ``_adam_kernel`` call, and views:

- ``m`` and ``v`` reach the kernel as ``bitcast``s of the program's
  parameters, so ``input_output_aliases`` update the state's own memory;
- the kernel's three outputs reach the program's results and the leaf
  slices through ``bitcast``s.

On the parent of PR 30 the same reading counts eight strays: two pads
(``pad_to_tiles`` of ``m`` and ``v``), three slices (``untile`` of the
three outputs) and three reshapes of the whole buffer, one for every
family of leaf shapes (``unflatten`` wrote ``slice(...).reshape(shape)``
and XLA moved the reshape above the slice).

All in this one file, inside fixtures, as the ``on-chip-measurement``
guide prescribes: only the worker that runs this file loads libtpu."""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from apex_tpu import models
from apex_tpu.optimizers import FusedAdam

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_tpu(monkeypatch):
    """The kernel's gate asks ``on_tpu()``; answer as the chip would."""
    from apex_tpu.ops import pallas_utils
    import apex_tpu.optimizers.fused_adam  # noqa: F401
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    monkeypatch.setattr(sys.modules["apex_tpu.optimizers.fused_adam"],
                        "on_tpu", lambda: True)
    # a compile for a described chip cannot be read back from the cache
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


# a result is one array or a tuple of them; a layout such as
# ``{1,0:T(8,128)}`` nests one pair of parentheses
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\((?:[^()]|\([^()]*\))*\)|\S+) "
    r"([\w-]+)\(([^)]*)\)")
_ARRAY = re.compile(r"\w+\[([\d,]*)\]")


def entry_instructions(text):
    """``{name: (element count of the largest array of the result,
    opcode, operand names, line)}`` over the entry computation of a
    compiled module."""
    entry = text[text.index("\nENTRY "):]
    out = {}
    for line in entry.splitlines()[2:]:
        if line.startswith("}"):
            break
        m = _INSTR.match(line)
        if m:
            sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
                     for dims in _ARRAY.findall(m.group(2))]
            out[m.group(1)] = (max(sizes, default=0), m.group(3),
                               re.findall(r"%([\w.\-]+)", m.group(4)), line)
    return out


def ancestors(instrs, name):
    seen, todo = set(), [name]
    while todo:
        n = todo.pop()
        if n not in seen and n in instrs:
            seen.add(n)
            todo.extend(instrs[n][2])
    return seen


VIEWS = {"bitcast", "parameter", "get-tuple-element", "tuple"}


def read_step(text, total):
    """How ``m`` and ``v`` reach the one ``_adam_kernel`` call (chains
    of opcodes back to a parameter), and the strays: what is
    ``total`` elements or more and neither a view, the kernel, nor a
    part of the two gathers."""
    instrs = entry_instructions(text)
    kernels = [n for n, (_, op, _, line) in instrs.items()
               if op == "custom-call" and "_adam_kernel" in line]
    assert len(kernels) == 1, kernels
    _, _, (_, p, m, v, g), _ = instrs[kernels[0]]

    def chain(name):
        ops = []
        while True:
            _, op, operands, _ = instrs[name]
            ops.append(op)
            if op == "parameter" or len(operands) != 1:
                return ops
            name = operands[0]

    gathers = ancestors(instrs, p) | ancestors(instrs, g)
    strays = [(n, op) for n, (size, op, _, _) in instrs.items()
              if size >= total and op not in VIEWS
              and n != kernels[0] and n not in gathers]
    return chain(m), chain(v), strays


def _shapes(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def _compile_step(opt, params, one_chip):
    state = jax.eval_shape(opt.init, params)
    step = jax.jit(
        lambda p, g, s: opt.step(p, g, s, skip=jnp.asarray(False)),
        donate_argnums=(0, 2))
    text = step.lower(_shapes(params, one_chip), _shapes(params, one_chip),
                      _shapes(state, one_chip)).compile().as_text()
    return text, state


def test_flat_step_moves_the_buffer_as_views(one_chip, as_on_tpu):
    cfg = dataclasses.replace(models.gpt_medium(), num_hidden_layers=2)
    params = jax.eval_shape(lambda: models.GPTLMHeadModel(cfg).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"])
    # minor dimensions of 64 (attention), 1,024 and 4,096, and 1-D leaves
    assert {x.shape[-1] for x in jax.tree.leaves(params)} == {
        64, 1024, 4096}
    text, state = _compile_step(FusedAdam(lr=3e-4), params, one_chip)
    total = state.spec.total
    assert state.m.shape == (total,) and total % 1024 == 0

    m_chain, v_chain, strays = read_step(text, total)
    assert m_chain == ["bitcast", "parameter"], m_chain
    assert v_chain == ["bitcast", "parameter"], v_chain
    assert strays == []


def test_a_length_that_is_no_multiple_of_128_compiles(one_chip, as_on_tpu):
    """The padded route: a classifier of 1,000 beside a convolution
    kernel, ``pad_to=1``, 38,269 elements."""
    params = {"conv": jax.ShapeDtypeStruct((7, 7, 3, 64), jnp.float32),
              "head": jax.ShapeDtypeStruct((28, 1000), jnp.float32),
              "bias": jax.ShapeDtypeStruct((861,), jnp.float32)}
    text, state = _compile_step(FusedAdam(lr=3e-4, pad_to=1), params,
                                one_chip)
    assert state.m.shape[0] % 128 != 0
    assert len([line for line in text.splitlines()
                if "custom-call(" in line and "_adam_kernel" in line]) == 1
