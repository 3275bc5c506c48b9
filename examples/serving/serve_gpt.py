"""Batched GPT inference: KV-cache + continuous batching demo.

The serving companion to ``examples/gpt`` — the same GPT family, but
the OTHER half of its life: randomly initialized (or checkpoint-
restored) params behind an :class:`apex_tpu.serving.InferenceServer`,
a burst of mixed-length requests, and the serving counters that
matter (tokens/s, batch occupancy, queue depth, compile counts).
Synthetic token prompts — the point is the serving machinery, not the
tokenizer.

    python examples/serving/serve_gpt.py --config tiny --requests 12
    python examples/serving/serve_gpt.py --config small \
        --batch-size 16 --max-new 128            # TPU
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import models
from apex_tpu.serving import InferenceServer
from apex_tpu.utils.compile_cache import enable_compile_cache


def parse_args():
    p = argparse.ArgumentParser(
        description="GPT batched inference (KV-cache + continuous "
        "batching)")
    p.add_argument("--config", default="tiny",
                   choices=["tiny", "small", "medium"])
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--max-new", type=int, default=48)
    p.add_argument("--batch-size", type=int, default=8,
                   help="decode slots (running requests per step)")
    p.add_argument("--block-size", type=int, default=16,
                   help="KV-cache block granularity (tokens)")
    p.add_argument("--max-context", type=int, default=None,
                   help="per-request token cap (default: the model's "
                   "max_position_embeddings)")
    p.add_argument("--checkpoint", default=None,
                   help="utils.checkpoint dir to restore params from "
                   "(default: random init)")
    p.add_argument("--tp", type=int, default=None, metavar="N",
                   help="tensor-parallel serving over the first N "
                   "devices (docs/serving.md, 'Tensor-parallel "
                   "serving'): params shard Megatron-style, the KV "
                   "pool shards its heads, decode runs GSPMD; greedy "
                   "output is bit-identical to unsharded")
    p.add_argument("--kv-quant", dest="kv_quant",
                   action="store_true",
                   help="store the KV pool int8-quantized with a "
                   "per-slot per-head fp32 scale sidecar — ~1.9x "
                   "live blocks per HBM byte at head_dim 64 "
                   "(docs/serving.md, 'Quantized KV cache')")
    p.add_argument("--disagg", action="store_true",
                   help="serve with DISAGGREGATED prefill/decode "
                   "pools: every prefill runs in a dedicated prefill "
                   "pool and hands its KV blocks to the pure-decode "
                   "pool via the cross-pool block copy "
                   "(docs/serving.md, 'Disaggregated prefill/"
                   "decode')")
    p.add_argument("--eos", type=int, default=None,
                   help="stop token id (default: run to --max-new)")
    p.add_argument("--ops-port", type=int, default=None,
                   help="serve the HTTP ops plane on this loopback "
                   "port while the demo runs (0 = ephemeral): curl "
                   "/healthz, /metrics, /statusz, /debug/flight "
                   "live, or point tools/ops_probe.py at it "
                   "(docs/observability.md)")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def build(args):
    if args.config == "tiny":
        cfg = models.GPTConfig(
            vocab_size=1024, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=256,
            max_position_embeddings=256, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0)
    elif args.config == "small":
        cfg = models.gpt_small()
    else:
        cfg = models.gpt_medium()
    m = models.GPTLMHeadModel(cfg)
    params = m.init(jax.random.PRNGKey(args.seed),
                    jnp.ones((1, 8), jnp.int32))["params"]
    if args.checkpoint:
        from apex_tpu.utils import checkpoint
        params = checkpoint.restore(args.checkpoint,
                                    {"params": params})["params"]
    return cfg, params


def main():
    args = parse_args()
    enable_compile_cache()
    cfg, params = build(args)
    mesh = None
    if args.tp:
        from jax.sharding import Mesh
        if len(jax.devices()) < args.tp:
            raise SystemExit(
                f"--tp {args.tp} needs {args.tp} devices, have "
                f"{len(jax.devices())} (on CPU, set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={args.tp})")
        mesh = Mesh(np.asarray(jax.devices()[:args.tp]), ("model",))

    server = InferenceServer(
        cfg, params, max_batch_size=args.batch_size,
        max_context=args.max_context, block_size=args.block_size,
        kv_quant="int8" if args.kv_quant else None,
        enable_disagg=args.disagg, ops_port=args.ops_port, mesh=mesh)
    if server.ops is not None:
        print(f"ops plane: http://127.0.0.1:{server.ops.port} "
              f"(/healthz /metrics /statusz /debug/flight)")
    if args.disagg:
        pk = server.prefill_engine.cache_cfg
        print(f"disaggregated pools: prefill {pk.num_blocks - 1} "
              f"blocks ({pk.bytes() / 2 ** 20:.1f} MiB) -> decode "
              f"pool (hand-off via cross-pool block copy)")
    kv = server.engine.cache_cfg
    store = ("int8+fp32 scales" if kv.quantized
             else kv.resolved_dtype().name)
    print(f"model={args.config} ({cfg.num_hidden_layers}x"
          f"{cfg.hidden_size})  kv pool: {kv.num_blocks - 1} blocks x "
          f"{kv.block_size} tokens, {store}, "
          f"{kv.bytes() / 2 ** 20:.1f} MiB")
    if mesh is not None:
        sh = server.engine.sharding_info()
        print(f"tensor parallel: tp={sh['tp']} over "
              f"{sh['devices']} devices "
              f"({sh['kv_pool_bytes_per_device'] / 2 ** 20:.1f} MiB "
              "KV per device)")

    rng = np.random.RandomState(args.seed)
    max_ctx = server.engine.max_context
    prompts = [list(rng.randint(0, cfg.vocab_size,
                                size=int(rng.randint(
                                    4, max(8, max_ctx // 4)))))
               for _ in range(args.requests)]

    # warm the compile caches (the one chunk program every prompt
    # goes through, plus the decode program) outside the timed window
    server.generate([[1] * min(server.prefill_chunk, max_ctx - 1)],
                    max_new_tokens=2)
    server.engine.reset_cache()
    server.reset_meters()

    t0 = time.perf_counter()
    outs = server.generate(prompts, max_new_tokens=args.max_new,
                           eos_id=args.eos)
    dt = time.perf_counter() - t0

    for i, (p, o) in enumerate(zip(prompts, outs)):
        head = " ".join(str(t) for t in o[:8])
        print(f"req {i:2d}: prompt[{len(p):3d}] -> {len(o):3d} tokens: "
              f"{head}{' ...' if len(o) > 8 else ''}")
    st = server.stats()
    sp = st["speculation"]
    spec = (f" | speculation: {sp['tokens_per_engine_step']:.2f} "
            f"tok/engine-step @ {sp['acceptance_rate']:.0%} accepted"
            if sp["enabled"] and sp["drafted_tokens"] else "")
    print(f"\n{st['tokens_generated']} tokens in {dt:.2f}s = "
          f"{st['tokens_generated'] / dt:.0f} tok/s | occupancy "
          f"{st['batch_occupancy_avg']:.0%} | queue peak "
          f"{st['queue_depth_peak']:.0f} | compiles: "
          f"{st['prefill_compiles']} prefill / {st['decode_compiles']} "
          f"decode | preemptions {st['preemptions']}{spec}")


if __name__ == "__main__":
    main()
