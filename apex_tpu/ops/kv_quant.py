"""Symmetric int8 absmax quantization primitives for the KV cache.

The serving stack's quantized-pool mode (``docs/serving.md``,
"Quantized KV cache") stores K/V as int8 with one fp32 scale per
(layer, token slot, head); these two functions are its ONLY numeric
contract, shared by every consumer so the bytes written, the values
attention reads, and the parity oracles all agree:

- :mod:`serving.kv_cache` quantizes nothing itself but re-exports
  these for the pool's scatter/gather plumbing and tests;
- :mod:`models.gpt` quantizes freshly-projected K/V at the source
  (``kv_quant=True``) so attention ALWAYS sees the dequantized grid —
  the self token, within-chunk keys, and cache reads alike — which is
  what makes quant-on generation bit-stable across chunking,
  preemption re-prefill, COW, and speculation (the same value
  quantizes to the same byte no matter how the writes were batched);
- :mod:`ops.decode_attention` widens int8 context back to the compute
  dtype in-kernel (the Pallas streaming kernel dequantizes each
  K-block in VMEM after the int8 HBM read; the jnp oracle dequantizes
  with the same fp32-multiply-then-single-cast rule).

Design notes: absmax maps to +/-127 (never -128) so the grid is
symmetric and negation-exact; all-zero vectors take scale 0 through a
gated inverse (no division, no NaN); the quantize/dequantize math runs
in fp32 regardless of compute dtype and casts exactly once on the way
out, so bf16 and fp32 compute paths disagree only by their final
rounding of the same fp32 product.
"""

from __future__ import annotations

import jax.numpy as jnp

# symmetric int8 quantization range: absmax maps to +/-127 (never
# -128, so negation stays exact and the grid is symmetric)
INT8_QMAX = 127.0


def quantize_kv(x):
    """Symmetric absmax int8 quantization over the LAST axis (the
    head_dim of a K/V vector): ``x`` (..., D) any float dtype ->
    ``(q int8 (..., D), scale fp32 (...))`` with
    ``q = round(x / scale)`` clipped to [-127, 127] and
    ``scale = absmax / 127``.

    All-zero vectors quantize to (0, scale=0) — the inverse scale is
    gated to 0 rather than dividing, so no NaN/inf ever enters the
    pool and :func:`dequantize_kv` returns exact zeros.  The math is
    elementwise per (token, head) vector, so the SAME value quantizes
    to the SAME bytes no matter how the writes were batched
    (chunks of any width, decode singles, verify columns) —
    the determinism every bit-stability oracle leans on."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / INT8_QMAX
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(xf * inv[..., None]), -INT8_QMAX,
                 INT8_QMAX).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype):
    """Widen int8 K/V back to the compute ``dtype``:
    ``q (..., D) int8, scale (...) fp32 -> (..., D) dtype``.  The
    multiply happens in fp32 and casts ONCE at the end, so a bf16 and
    an fp32 compute path see the same fp32 product before their
    respective roundings (pinned by ``tests/L0/test_kv_quant.py``)."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)
