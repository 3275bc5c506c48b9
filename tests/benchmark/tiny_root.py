"""A copy of the benchmark with tiny cells added AS NEW FILES ONLY: what
a later PR may do.  The CPU tests run these cells through the real
command line's ``main``; ``record_fixture.py`` runs them on the chip to
record the small trace the reducer is checked on.

Two configurations come in: ``gpt2-tiny`` in GPT-2's own key names,
which takes the GPT-2 files that are there, and ``hf-tiny``, whose file
holds none of those names and which brings its own ``program`` and
``reference`` files: the harness reads no model's keys, so a
configuration of another key dialect is files alone."""

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_SIZES = {
    "name": "gpt2-tiny", "source": "a test's own sizes",
    "attn_pdrop": 0.0, "embd_pdrop": 0.0, "resid_pdrop": 0.0,
    "initializer_range": 0.02, "layer_norm_epsilon": 1e-05,
    "n_embd": 64, "n_head": 2, "n_inner": None, "n_layer": 2,
    "n_positions": 256, "vocab_size": 512, "reduced": [],
    "reference": "benchmarks/configs/gpt2-tiny.reference.py",
    "program": "benchmarks/configs/gpt2.program.py",
    "optimizer": {"name": "adam", "lr": 0.0003, "betas": [0.9, 0.999],
                  "eps": 1e-08, "weight_decay": 0.0},
    # set as the cells' own are, from readings at this size on the CPU:
    # six seeds of the program read at most 2.7e-5, 0.0055 and 0.0126;
    # the fp8 control at least 5e-5 (first loss), 0.0085 and 0.011, half
    # a batch 0.39 and 0.10
    "limits": {"train": {"loss1_gap": 4e-05, "loss2_gap": 4e-05,
                         "loss3_gap": 4e-05, "grad_norm_gap": 0.008,
                         "delta_norm_gap": 0.02},
               # served by the bfloat16 program: 0.0011 at most; the token
               # fp8 puts first: 0.0097 over 60 positions
               "serve": {"served_gap_max": 0.004}},
}

TINY_TRAFFIC = {
    "tiny-docs": {
        "runner": "train", "batch_per_chip": 2, "seq": 128,
        "documents": {"dist": "lognormal", "median": 40, "sigma": 1.0,
                      "min": 4, "max": 512},
        "separator_id": 511, "reference_rows": 2,
        "trace": {"start_fraction": 0.2, "seconds": 0.5}},
    "tiny-chat": {
        "runner": "serve", "loop": "open", "rate_per_s": 30.0,
        "prompt": {"dist": "lognormal", "median": 24, "sigma": 1.0,
                   "min": 4, "max": 100},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                   "min": 2, "max": 24},
        "max_total": 128, "sampling": {"temperature": 0.8, "top_p": 0.95},
        "greedy_share": 0.25, "shape_seed": 7, "stratum": 8,
        "fill_s": 0.5,
        "server": {"max_batch_size": 4, "max_context": 128},
        "check": {"requests": 4, "sampled_requests": 4,
                  "rows_per_block": 2},
        "trace": {"ends_with_window": True, "seconds": 0.5}},
    "tiny-backlog": {
        "runner": "serve", "loop": "closed", "clients": 6,
        "prompt": {"dist": "uniform", "min": 40, "max": 100},
        "output": {"dist": "uniform", "min": 4, "max": 12},
        "max_total": 128, "greedy_share": 1.0, "shape_seed": 8,
        "stratum": 6, "fill_s": 0.5, "planned_requests": 706,
        "server": {"max_batch_size": 4, "max_context": 128},
        "check": {"requests": 4, "rows_per_block": 2},
        "trace": {"start_fraction": 0.2, "seconds": 0.5}},
}

NEW_METRIC = ('"""A metric a later PR brings: steps per second."""\n\n\n'
              'def read(ctx):\n'
              '    n, lo, hi = ctx["trace"].whole_launches("jit_step")\n'
              '    return n / (hi - lo) if n else None\n')


# the same tiny decoder in another key dialect: none of GPT-2's names
HF_SIZES = {
    "name": "hf-tiny", "source": "a test's own sizes",
    "hidden_size": 64, "num_attention_heads": 2, "num_hidden_layers": 2,
    "intermediate_size": 256, "max_position_embeddings": 256,
    "vocab_size": 512, "norm_eps": 1e-05, "init_std": 0.02, "reduced": [],
    "reference": "benchmarks/configs/hf-tiny.reference.py",
    "program": "benchmarks/configs/hf-tiny.program.py",
    "optimizer": TINY_SIZES["optimizer"], "limits": TINY_SIZES["limits"],
}

HF_PROGRAM = '''"""``hf-tiny`` onto the program's only decoder."""


def model_config(models, sizes):
    return models.GPTConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        num_attention_heads=sizes["num_attention_heads"],
        intermediate_size=sizes["intermediate_size"],
        max_position_embeddings=sizes["max_position_embeddings"],
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        layer_norm_eps=sizes["norm_eps"],
        initializer_range=sizes["init_std"])
'''

HF_REFERENCE = '''"""``hf-tiny``'s reference and counts: the published
GPT-2 equations, with this configuration's keys translated for them."""

from benchmarks.reference import gpt2, gpt2_counts


def _keys(sizes):
    return {"vocab_size": sizes["vocab_size"],
            "n_embd": sizes["hidden_size"],
            "n_head": sizes["num_attention_heads"],
            "n_layer": sizes["num_hidden_layers"],
            "n_inner": sizes["intermediate_size"],
            "n_positions": sizes["max_position_embeddings"],
            "layer_norm_epsilon": sizes["norm_eps"],
            "initializer_range": sizes["init_std"]}


def _translated(fn):
    def call(*args, **kwargs):
        return fn(*[_keys(a) if isinstance(a, dict) and "hidden_size" in a
                    else a for a in args], **kwargs)
    return call


for _module, _names in (
        (gpt2, ("logit_at", "logits", "longest_row", "mass_above",
                "next_token_loss", "param_table", "stacked", "token_gaps",
                "train_steps", "unstacked_leaf_norms", "vocab",
                "weight_std")),
        (gpt2_counts, ("adam_bytes", "attention_flops_causal",
                       "decode_attention_bytes", "flash_train_flops_bytes",
                       "forward_flops_at", "matmul_params", "total_params",
                       "train_flops_per_sequence"))):
    for _name in _names:
        globals()[_name] = _translated(getattr(_module, _name))
'''

# requests that share a prefix: each document asked 2 or 3 times
SHARED_TRAFFIC = {
    "tiny-shared": {
        "runner": "serve", "loop": "closed", "clients": 6,
        "prompt": {"dist": "uniform", "min": 40, "max": 80},
        "shared": {"asks": {"dist": "uniform", "min": 2, "max": 3},
                   "suffix": {"dist": "uniform", "min": 4, "max": 12},
                   "apart": 3},
        "output": {"dist": "uniform", "min": 4, "max": 8},
        "max_total": 128, "greedy_share": 1.0, "shape_seed": 9,
        "stratum": 6, "fill_s": 0.5, "planned_requests": 706,
        "server": {"max_batch_size": 4, "max_context": 128},
        "check": {"requests": 4, "rows_per_block": 2},
        "trace": {"ends_with_window": True, "seconds": 0.5}},
}

# (cell, configuration, traffic mix, the accepted cell whose metrics it
# reports)
CELLS = [("tiny-train", "gpt2-tiny", "tiny-docs", "train"),
         ("tiny-chat", "gpt2-tiny", "tiny-chat", "chat"),
         ("tiny-backlog", "gpt2-tiny", "tiny-backlog", "backlog"),
         ("tiny-shared", "gpt2-tiny", "tiny-shared", "backlog"),
         ("hf-train", "hf-tiny", "tiny-docs", "train"),
         ("hf-chat", "hf-tiny", "tiny-chat", "chat"),
         ("hf-backlog", "hf-tiny", "tiny-backlog", "backlog")]


SMALL_SIZES = dict(TINY_SIZES, n_embd=256, n_head=4, n_positions=1024,
                   vocab_size=2048)


def small_traffic(trace_seconds):
    """The tiny mixes at shapes the Pallas kernels take (sequences past
    the flash gate's 512, heads of 64), for the chip's fixture trace."""
    t = {k: dict(v, trace=dict(v["trace"], start_fraction=0.3,
                               seconds=trace_seconds))
         for k, v in TINY_TRAFFIC.items()}
    t["tiny-docs"].update(seq=1024, separator_id=2047)
    for k in ("tiny-chat", "tiny-backlog"):
        t[k].update(max_total=256,
                    server={"max_batch_size": 4, "max_context": 256})
    return t


def make(tmp, chips=1, sizes=TINY_SIZES, mixes=TINY_TRAFFIC):
    """Copy ``BENCHMARK.json`` and ``benchmarks/`` into ``tmp``, then
    add two configurations, four traffic mixes, a metric and seven
    cells, touching no file that was there except to append entries."""
    tmp = str(tmp)
    before = {}
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for d, _, files in os.walk(os.path.join(tmp, "benchmarks")):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    b = os.path.join(tmp, "benchmarks")

    def write(path, text):
        with open(os.path.join(b, path), "x") as f:    # never one there
            f.write(text)

    write("configs/gpt2-tiny.json", json.dumps(sizes))
    shutil.copy(os.path.join(b, "configs", "gpt2-medium.reference.py"),
                os.path.join(b, "configs", "gpt2-tiny.reference.py"))
    write("configs/hf-tiny.json", json.dumps(HF_SIZES))
    write("configs/hf-tiny.program.py", HF_PROGRAM)
    write("configs/hf-tiny.reference.py", HF_REFERENCE)
    for name, mix in dict(SHARED_TRAFFIC, **mixes).items():
        write(f"workloads/{name}.json", json.dumps(mix))
    write("metrics/steps_per_s.tiny.py", NEW_METRIC)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name in ("gpt2-tiny", "hf-tiny"):
        bench["configs"].append({
            "name": name, "source": "a test", "reduced": [],
            "file": f"benchmarks/configs/{name}.json", "why": "a test"})
    for cell, config, mix, _ in CELLS:
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": mix, "chips": chips,
                                   "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        kind = m["name"].rsplit(".", 1)[-1]
        if m["name"] == "train_tokens_per_s" or kind in ("train", "ddp4"):
            of = "train" if kind != "ddp4" or chips > 1 else None
        elif m["name"] in ("ttft_p95_ms", "itl_p50_ms") or kind == "chat":
            of = "chat"
        elif m["name"] == "serve_tokens_per_s" or kind == "backlog":
            of = "backlog"
        else:
            of = None
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [
                cell for cell, _, _, like in CELLS if like == of]
    bench["per_layer"].append({
        "name": "steps_per_s.tiny", "unit": "steps/s", "better": "higher",
        "source": "device_trace", "layer": "trainer step",
        "moves": "train_tokens_per_s", "workloads": ["tiny-train"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return tmp, before


def unchanged(before):
    """Every file that was there still holds what it held."""
    for path, data in before.items():
        with open(path, "rb") as fh:
            if fh.read() != data:
                return path
    return None
