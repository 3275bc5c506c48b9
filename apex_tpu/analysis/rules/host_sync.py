"""``host-sync`` — no hidden device→host syncs in PLAN/LAUNCH code.

The invariant (PR 8, docs/serving.md "Pipelined serve loop"): between
LAUNCH and the next RETIRE the host must make every scheduling
decision *without materializing a device value*.  One stray
``np.asarray(ids)`` / ``.item()`` / ``float(x)`` on a traced value
blocks the host on the device step it just dispatched — the loop is
silently synchronous again and the ~17% overlap win evaporates, with
no test failing (output is bit-identical either way; only the chaos
soak's wall clock notices, and only if someone reads it).

Two tiers:

1. Inside the **hot functions** (the PLAN/LAUNCH body of
   ``InferenceServer._step`` and the launch helpers, plus every
   jitted program body — ``*_impl`` — where a host-numpy call means a
   concretization during trace): flag ``.item()`` / ``.tolist()`` /
   ``.block_until_ready()``, host-numpy materializers
   (``np.asarray`` / ``np.array`` / ``np.all`` / ``np.any`` /
   ``np.isfinite`` / ``np.argmax``), and ``float()/int()/bool()``
   over non-literal expressions (implicit scalar materialization —
   the same class as implicit array truthiness).
2. Anywhere in the scoped modules: ``jax.device_get`` /
   ``jax.block_until_ready`` — unconditional syncs that belong only
   in the documented RETIRE path (``allow_functions``).

Legitimate sync points carry ``# apexlint: disable=host-sync`` with a
justification (e.g. the prefill token that gates same-iteration
decode admission is synchronous *by design*).
"""

from __future__ import annotations

import ast
from typing import List

from ..core import Finding, SourceModule, in_scope

name = "host-sync"
summary = ("device→host syncs reachable from PLAN/LAUNCH re-serialize "
           "the pipelined serve loop")

default_options = {
    "paths": ["apex_tpu/serving/api.py", "apex_tpu/serving/engine.py"],
    # PLAN/LAUNCH bodies; every *_impl function (the jitted program
    # bodies) is hot implicitly via impl_suffix
    "hot_functions": ["_step", "_admit", "_launch_chunk",
                      "_launch_decode", "_launch_verify",
                      "_decode_inputs", "_verify_inputs"],
    "impl_suffix": "_impl",
    # the documented RETIRE/materialization points, exempt from the
    # module-wide device_get/block_until_ready tier
    "allow_functions": ["_flush_window"],
}

_SYNC_CALLS = {"jax.device_get", "jax.block_until_ready"}
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_NUMPY_MATERIALIZERS = {"asarray", "array", "all", "any", "isfinite",
                        "argmax"}
_SCALAR_BUILTINS = {"float", "int", "bool"}


def _is_host_literalish(node: ast.AST) -> bool:
    """Expressions that cannot hold a device value: literals, len(),
    pure arithmetic over those, and attribute reads of shapes."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id in ("len", "min", "max", "round"):
        return True
    if isinstance(node, ast.BinOp):
        return (_is_host_literalish(node.left)
                and _is_host_literalish(node.right))
    if isinstance(node, ast.UnaryOp):
        return _is_host_literalish(node.operand)
    if isinstance(node, ast.Attribute) and node.attr in ("shape",
                                                         "ndim", "size"):
        return True
    if isinstance(node, ast.Subscript):
        return _is_host_literalish(node.value)
    return False


def check(mod: SourceModule, options: dict) -> List[Finding]:
    findings: List[Finding] = []
    hot = set(options.get("hot_functions", ()))
    impl_suffix = options.get("impl_suffix", "_impl")
    allow = set(options.get("allow_functions", ()))

    for fn in ast.walk(mod.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        is_hot = fn.name in hot or (impl_suffix
                                    and fn.name.endswith(impl_suffix))
        in_impl = bool(impl_suffix) and fn.name.endswith(impl_suffix)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            resolved = mod.resolve(node.func)
            if resolved in _SYNC_CALLS and fn.name not in allow:
                findings.append(mod.finding(
                    name, node,
                    f"{resolved} is an unconditional device sync; "
                    f"only the RETIRE path "
                    f"({', '.join(sorted(allow)) or 'none'}) may "
                    f"materialize launched results"))
                continue
            if not is_hot:
                continue
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _SYNC_METHODS \
                    and not node.args:
                findings.append(mod.finding(
                    name, node,
                    f".{node.func.attr}() materializes a device "
                    f"value inside a PLAN/LAUNCH section; move it to "
                    f"RETIRE or justify with a pragma"))
                continue
            if resolved and resolved.startswith("numpy.") \
                    and resolved.split(".", 1)[1] in \
                    _NUMPY_MATERIALIZERS:
                where = ("inside a jitted program body (a "
                         "concretization error waiting for a traced "
                         "input)" if in_impl
                         else "inside a PLAN/LAUNCH section (blocks "
                         "the host on the in-flight device step)")
                findings.append(mod.finding(
                    name, node,
                    f"{resolved} on a potentially traced value "
                    f"{where}"))
                continue
            if isinstance(node.func, ast.Name) \
                    and node.func.id in _SCALAR_BUILTINS \
                    and len(node.args) == 1 \
                    and not _is_host_literalish(node.args[0]):
                findings.append(mod.finding(
                    name, node,
                    f"{node.func.id}(...) over a non-literal in a "
                    f"PLAN/LAUNCH section is an implicit scalar "
                    f"materialization (same class as array "
                    f"truthiness); keep decisions on host state or "
                    f"move to RETIRE"))
    return findings


def applies(relpath: str, options: dict) -> bool:
    return in_scope(relpath, options.get("paths", []))
