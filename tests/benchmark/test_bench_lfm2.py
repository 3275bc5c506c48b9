"""The ``lfm2-24b-a2b`` configuration and its cell ``lfm2-reason-backlog``:
the file against the catalog's keys, the counts by hand and against a
spelt-out loop, the mix, a tiny cell of the family through the command
line's ``main`` on the CPU, and the readers on a synthetic trace.  The
tiny cell comes in as files and entries alone, on top of ``tiny_root``'s
copy of the benchmark."""

import json
import os
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_root  # noqa: E402
from benchmarks import run  # noqa: E402
from benchmarks.harness import line, spec  # noqa: E402
from benchmarks.harness.spans import Span  # noqa: E402
from benchmarks.harness.trace import Line, Trace  # noqa: E402

ROOT = tiny_root.ROOT
CELL = "lfm2-reason-backlog"
# the published ``config.json`` as the model-configs catalog records it
PUBLISHED = json.loads('{"source_url": "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json", "config": {"conv_L_cache": 3, "conv_bias": false, "hidden_size": 2048, "intermediate_size": 11776, "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv"], "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": true, "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 8, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 1, "use_expert_bias": true, "vocab_size": 65536}}')
REDUCED = {"num_hidden_layers", "layer_types", "num_dense_layers"}
HERE = ["conv", "full_attention", "conv", "conv", "conv", "full_attention",
        "conv", "conv", "conv"]


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


# -- the file against the catalog ------------------------------------------------

def test_the_configuration_holds_the_published_widths(cell):
    row = PUBLISHED
    ours = cell.config
    differ = {k for k, v in row["config"].items() if ours.get(k) != v}
    assert differ == REDUCED == set(ours["reduced"])
    assert ours["published"] == {k: row["config"][k] for k in REDUCED}
    assert (ours["num_hidden_layers"], ours["num_dense_layers"]) == (9, 1)
    # published layer 0 and layers 2 to 9: two whole periods
    assert ours["layer_types"] == HERE \
        == [row["config"]["layer_types"][0]] \
        + row["config"]["layer_types"][2:10]
    # every width as published
    assert (ours["hidden_size"], ours["num_attention_heads"],
            ours["num_key_value_heads"], ours["intermediate_size"],
            ours["moe_intermediate_size"], ours["num_experts"],
            ours["num_experts_per_tok"], ours["conv_L_cache"],
            ours["vocab_size"]) \
        == (2048, 32, 8, 11776, 1536, 64, 4, 3, 65536)
    assert ours["source"] == row["source_url"]
    for key in ("deployment", "cut_why", "assumed", "assumed_why",
                "departures", "precision", "cache", "limits", "limits_why",
                "decided_margin"):
        assert ours[key], key
    assert set(ours["assumed"]) >= {"initializer_range", "tie_word_embeddings",
                                    "scoring_func", "topk_norm_eps"}
    assert ours["assumed"]["topk_norm_eps"] == 1e-6
    assert ours["reference_longest_row"] == cell.traffic["max_total"] \
        == cell.traffic["server"]["max_context"] == 6144
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == cell.config_name)
    assert set(entry["reduced"]) == REDUCED
    mine = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert mine["chips"] == 1 and len(mine["why"]) <= 200


def test_the_program_file_maps_the_keys_and_refuses_what_it_cannot(cell):
    from apex_tpu import models
    cfg = cell.model_config(models)
    assert (cfg.num_experts, cfg.vocab_size, cfg.num_hidden_layers,
            cfg.head_dim, cfg.rope_theta) == (64, 65536, 9, 64, 1e6)
    assert cfg.layer_states() == tuple(
        (2, 2048) if k == "conv" else None for k in HERE)
    row = cfg.cache_row()
    assert (row.groups, row.group_width, row.heads_per_group) == (8, 128, 4)
    spec_ = cfg.experts_spec()
    assert (spec_.shared_width, spec_.norm_eps, spec_.top_k,
            spec_.scaling) == (0, 1e-6, 4, 1.0)
    program = spec.load_module(os.path.join(ROOT, cell.config["program"]))
    for key, other in (("conv_bias", True), ("use_expert_bias", False),
                       ("rope_parameters", {"rope_type": "yarn",
                                            "rope_theta": 1e6})):
        with pytest.raises(SystemExit, match="cannot express"):
            program.model_config(models, dict(cell.config, **{key: other}))

    class Parent:        # a checkout from before the family existed
        pass

    with pytest.raises(SystemExit, match="no lfm2_moe family"):
        program.model_config(Parent, cell.config)


# -- the counts by hand ----------------------------------------------------------

def test_parameter_counts_by_hand(cell):
    ref, s = cell.reference(), cell.config
    p = ref.params_by_part(s)
    assert p["conv"] == 3 * 2048 ** 2 + 2048 ** 2 + 3 * 2048
    assert round(p["conv"] / 1e6, 2) == 16.78
    assert round(p["attention"] / 1e6, 2) == 10.49
    assert round(p["expert"] / 1e6, 2) == 9.44
    assert round(64 * p["expert"] / 1e6, 2) == 603.98
    assert round(p["router"] / 1e6, 2) == 0.13
    assert round(p["dense_ff"] / 1e6, 2) == 72.35
    assert round(p["embedding"] / 1e6, 1) == 134.2
    layer = 64 * p["expert"] + p["router"]
    assert round((p["conv"] + p["dense_ff"]) / 1e6, 1) == 89.1
    assert round((p["conv"] + layer) / 1e6, 1) == 620.9
    assert round((p["attention"] + layer) / 1e6, 1) == 614.6
    assert p["total"] == 5_177_911_808
    # what the table draws is what is counted, the norms' weights aside
    drawn = sum(int(np.prod(shape)) for shape, kind in
                ref.param_table(s).values() if kind != "ones")
    assert drawn == ref.total_params(s)
    assert round(drawn * 2 / 1e9, 2) == 10.36
    # the model as published: 23.84B
    whole = dict(s, **s["published"])
    assert round(ref.total_params(whole) / 1e9, 2) == 23.84
    # the pool and the state as the file states them
    assert ref.row_bytes(s) == 2048
    cache = s["cache"]
    assert cache["full_attention"]["bytes_64_slots_of_6144"] \
        == 2 * 64 * 6144 * ref.row_bytes(s)
    assert cache["conv"]["bytes_64_slots"] == 7 * 64 * 16 * 2048 * 4
    assert ref.state_bytes_a_slot(s) == 7 * 2 * 2048 * 4


TINY_COUNTS = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "num_dense_layers": 1, "num_hidden_layers": 5,
    "vocab_size": 211, "conv_L_cache": 3,
    "layer_types": ["conv", "full_attention", "conv", "conv",
                    "full_attention"]}


@pytest.mark.parametrize("first,last", [(0, 1), (0, 5), (3, 8), (5, 21),
                                        (40, 41), (17, 17)])
def test_the_counts_against_a_spelt_out_loop(cell, first, last):
    """Token by token and key by key at a tiny size: every query head's
    score and value row for each key a token sees in the 2 attention
    layers; the conv layers' products, taps and gate; the products a
    token goes through; the head once a span."""
    ref, s = cell.reference(), TINY_COUNTS
    attn = 0
    for p in range(first, last):
        for _key in range(p + 1):
            attn += 2 * 2 * 4 * 16 * 2                  # 2 attention layers
    assert ref.attention_flops(s, first, last) == attn
    conv = 4 * 64 * 64
    attention = 64 * 16 * 2 * (4 + 2)
    expert = 3 * 64 * 32
    a_token = 3 * conv + 2 * attention + 3 * 64 * 96 + 4 * (
        2 * expert + 64 * 8)
    assert ref.active_matmul_params(s) == a_token
    n = last - first
    taps = 3 * (2 * 3 + 1) * 64
    assert ref.forward_flops_at(s, first, last) == (
        2 * a_token * n + attn + taps * n + 2 * 64 * 211 if n else 0)
    spans = [(first, last), (2, 2), (0, 3)]
    assert ref.attention_flops_bytes(s, spans, 100) \
        == (attn + ref.attention_flops(s, 0, 3), 2 * 100 * 2 * 32 * 2)
    # 10 tokens through 2 experts of 4 layers; a launch of one row reads
    # the 2 experts it chose, one of 100 rows all 8 (nearly)
    ops, nbytes = ref.moe_gmm_flops_bytes(s, 10, [1, 100])
    assert ops == 2 * 10 * 2 * expert * 4
    assert nbytes == pytest.approx(
        4 * expert * 2 * (2 + 8 * (1 - 0.75 ** 100)))
    # 12 tokens fed over 5 live launch rows, 3 conv layers
    assert ref.short_conv_flops_bytes(s, 12, 5) == (
        3 * 7 * 64 * 12, 3 * 64 * (12 * (6 + 2 + 4) + 5 * 2 * 4))


def test_forward_flops_at_the_cells_sizes_by_hand(cell):
    ref, s = cell.reference(), cell.config
    per_token = 2 * ref.active_matmul_params(s)
    head = 2 * 2048 * 65536
    per_key = 2 * 32 * 2 * 64
    taps = 7 * 7 * 2048
    # a decode step at position 2,000: 2,001 keys in the 2 attention
    # layers
    assert ref.forward_flops_at(s, 2000, 2001) == per_token + head + taps \
        + per_key * 2 * 2001
    # a chunk of 256 from 1,024
    assert ref.forward_flops_at(s, 1024, 1280) == 256 * (per_token + taps) \
        + head + per_key * 2 * sum(range(1025, 1281))
    # 513.8M parameters of products a token: the 7 conv mixers, the 2
    # attention layers, the dense layer, 4 experts and the router of 8
    assert round(per_token / 2e6, 1) == 513.8


def test_every_metric_of_the_cell_has_its_reader(cell):
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    assert sorted(names) == sorted(n + ".reason" for n in (
        "step_mfu", "short_conv_roofline", "attn_roofline",
        "moe_gmm_roofline", "decode_program_ms", "chunk_program_ms",
        "device_idle_pct", "step_idle_ms", "itl_p50_ms", "ttft_p50_ms",
        "kv_blocks_used_pct", "decode_rows_mean", "idle_unattributed_pct",
        "chunk_dispatch_ms"))
    for n in names:
        assert callable(cell.reader(n)), n


def test_the_mix_is_the_one_the_cell_states(cell):
    mix = cell.traffic
    assert (mix["loop"], mix["clients"], mix["stratum"]) == ("closed", 96,
                                                             96)
    assert mix["prompt"]["dist"] == mix["output"]["dist"] == "lognormal"
    assert (mix["prompt"]["median"], mix["prompt"]["min"],
            mix["prompt"]["max"]) == (1024, 128, 4096)
    assert mix["prompt"]["sigma"] in (0.8, 0.6)     # the one narrowing
    assert mix["output"] == {"dist": "lognormal", "median": 768,
                             "sigma": 0.5, "min": 256, "max": 1536}
    assert mix["sampling"] == {"temperature": 0.8, "top_p": 0.95}
    assert mix["greedy_share"] == 0.125
    assert mix["max_total"] == 6144 and "shared" not in mix
    assert mix["server"] == {"max_batch_size": 64, "max_context": 6144}
    assert mix["fill_s"] == 20.0
    assert mix["trace"] == {"ends_with_window": True, "seconds": 3.0}
    from benchmarks.harness import traffic
    n = mix["planned_requests"]
    plan = traffic.plan_requests(mix, 2 ** 31 + 3, n, 65536)
    prompts = np.asarray([len(p.prompt) for p in plan])
    outputs = np.asarray([p.max_new for p in plan])
    assert prompts.min() >= 128 and prompts.max() <= 4096
    assert outputs.min() >= 256 and outputs.max() <= 1536
    # about 1,400 at sigma 0.8, about 1,230 at 0.6; outputs about 870
    assert (1150 < prompts.mean() < 1300 if mix["prompt"]["sigma"] == 0.6
            else 1250 < prompts.mean() < 1550)
    assert 820 < outputs.mean() < 920
    assert sum(p.greedy for p in plan) == n // 8
    assert max(max(p.prompt) for p in plan) >= 60000    # the whole table


# -- a tiny cell of the family through the command line's main ------------------

TINY = {
    "name": "lfm2-tiny", "source": "a test's own sizes",
    "model_type": "lfm2_moe", "conv_bias": False, "use_expert_bias": True,
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "num_dense_layers": 1,
    "conv_L_cache": 3,
    "layer_types": ["conv", "full_attention", "conv", "conv",
                    "full_attention"],
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "norm_eps": 1e-05, "routed_scaling_factor": 1, "norm_topk_prob": True,
    "max_position_embeddings": 256, "reduced": [],
    "assumed": {"initializer_range": 0.02}, "reference_longest_row": 128,
    "reference": "benchmarks/configs/lfm2-tiny.reference.py",
    "program": "benchmarks/configs/lfm2_moe.program.py",
    # readings at this size on the CPU, four seeds: the program 0.0, and
    # the token fp8 puts first 0.0 too (the tied head at weights of 0.02
    # decides these greedy tokens by wide margins; at 0.1 a route flips
    # now and then, 0.42 on one seed of four).  So this limit tells a
    # wrong token from a right one and no precision from another: the
    # cell's own limit is set from the chip's readings (PERF.md)
    "limits": {"serve": {"served_gap_max": 0.01}},
}

TINY_MIX = {
    # as many clients as slots: nothing waits, so a loaded CPU cannot
    # make a request outwait a turn of the slots and count as failed
    "runner": "serve", "loop": "closed", "clients": 4,
    "prompt": {"dist": "lognormal", "median": 30, "sigma": 0.8, "min": 6,
               "max": 100},
    "output": {"dist": "uniform", "min": 4, "max": 12},
    "max_total": 128, "sampling": {"temperature": 0.8, "top_p": 0.95},
    "greedy_share": 0.5, "shape_seed": 8, "stratum": 6, "fill_s": 0.5,
    "planned_requests": 706,
    "server": {"max_batch_size": 4, "max_context": 128},
    "check": {"requests": 4, "sampled_requests": 2, "rows_per_block": 2},
    "trace": {"ends_with_window": True, "seconds": 0.5}}


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """``tiny_root``'s copy of the benchmark, and on top of it a tiny
    configuration of this family with a mix and a cell, as new files and
    entries."""
    root, before = tiny_root.make(tmp_path_factory.mktemp("lfm2"))
    b = os.path.join(root, "benchmarks")
    with open(os.path.join(b, "configs", "lfm2-tiny.json"), "x") as f:
        json.dump(TINY, f)
    with open(os.path.join(b, "configs", "lfm2-24b-a2b.reference.py")) as f:
        text = f.read()
    with open(os.path.join(b, "configs", "lfm2-tiny.reference.py"),
              "x") as f:
        f.write(text)
    with open(os.path.join(b, "workloads", "tiny-reason.json"), "x") as f:
        json.dump(TINY_MIX, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "lfm2-tiny", "source": "a test", "reduced": [],
        "file": "benchmarks/configs/lfm2-tiny.json", "why": "a test"})
    bench["workloads"].append({
        "name": "lfm2-tiny-reason", "config": "lfm2-tiny",
        "traffic": "tiny-reason", "chips": 1, "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("lfm2-tiny-reason")
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root, before


def drive(root, capsys, seed):
    capsys.readouterr()
    assert run.main(["--workload", "lfm2-tiny-reason", "--seed",
                     str(seed), "--seconds", "1", "--trace", "0"],
                    root=root, require_chip=False) == 0
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


def test_a_tiny_cell_of_the_family_runs_and_is_correct(added, capsys):
    root, before = added
    last, err = drive(root, capsys, 2 ** 31 + 5)
    assert last["correct"] is True, err[-2000:]
    assert set(last["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert last["attempted"] > 0 and last["failed"] == 0
    assert all(v["value"] > 0 for v in last["metrics"].values())
    for family in ("chunk_prefill_stoch", "decode_stoch", "verify_stoch"):
        assert family in err          # the warm-up found every program
    # no prefix hit is taken for a model whose layers keep a state
    assert "found in the prefix cache 0 and not 0" in err
    line.check_line(last, spec.load_cell("lfm2-tiny-reason", root), False)
    assert tiny_root.unchanged(before) is None


def test_a_token_altered_where_it_is_produced_is_not_correct(
        added, capsys, monkeypatch):
    from apex_tpu.serving import scheduler
    real = scheduler.Request.record_token
    count = [0]

    def altered(self, token):
        count[0] += 1
        return real(self, (int(token) + 1) % 512 if count[0] % 5 == 0
                    else token)

    monkeypatch.setattr(scheduler.Request, "record_token", altered)
    last, _ = drive(added[0], capsys, 11)
    assert last["correct"] is False
    got = last["compared"]["served_gap_max"]
    assert got["value"] > 10 * got["limit"]


# -- the readers on a synthetic trace ----------------------------------------------

def _trace(kernel_events, window=(10.0, 13.0)):
    """A trace whose one chip ran ``kernel_events`` ((name, start,
    seconds)) inside one launch of a chunk program."""
    lo, hi = window
    ops = Line([f"%{n} = bf16[256,2048]{{1,0}} custom-call(...)"
                for n, _, _ in kernel_events],
               np.array([s for _, s, _ in kernel_events], float),
               np.array([s + d for _, s, d in kernel_events], float))
    mods = Line(["jit__chunk_stoch_impl(1)"], np.array([lo + 0.1]),
                np.array([hi - 0.1]))
    host = Line(["bench_window"], np.array([lo]), np.array([hi]))
    return Trace({"/device:TPU:0": {"ops": ops, "modules": mods}}, lo, hi,
                 host)


def _spans(chunks, steps):
    """The program's spans a sub-window holds: ``chunks`` chunk launches
    of their tokens, ``steps`` decode or verify launches of (running
    requests, drafted tokens)."""
    out = [Span("chunk_prefill", 10.0, 10.01, i + 1, 0, {"tokens": t})
           for i, t in enumerate(chunks)]
    out += [Span("launch", 11.0, 11.01, 100 + i, 0,
                 {"program": "verify" if d else "decode", "batch": b,
                  **({"drafted": d} if d else {})})
            for i, (b, d) in enumerate(steps)]
    return types.SimpleNamespace(spans=out)


def _ctx(cell, trace, before, after, steps=(), program_spans=None):
    return {"trace": trace, "ref": cell.reference(), "sizes": cell.config,
            "device_kind": "TPU v5 lite", "chips": 1, "cell": cell,
            "program_spans": program_spans,
            "run": {"steps": list(steps), "slots": 64,
                    "sub": {"open": {"at": 10.0, "cached": before},
                            "close": {"at": 13.0, "cached": after}}}}


def test_the_roofline_readers_stay_under_100_at_the_least_time(cell):
    """Kernels that took exactly the least time the counts allow read
    100; any real kernel is slower and reads under it.  Four chunks of
    256 tokens at 1,024 and 40 decode launches of 60 running requests,
    10 verify launches of 60 requests and 40 drafts; the harness counts
    no cached rows for a verify launch, and the reader takes the decode
    launch's before it."""
    ref, s = cell.reference(), cell.config
    before, after = [1024, 2000], [1024 + 1024, 2050]
    steps = [(10.0 + 0.05 * i, 10.01 + 0.05 * i, 60, "decode", 120000, 5)
             for i in range(40)]
    steps += [(12.0 + 0.05 * i, 12.01 + 0.05 * i, 60, "verify", 0, 5)
              for i in range(10)]
    program = _spans([256] * 4, [(60, 0)] * 40 + [(60, 40)] * 10)
    tokens = 1024 + 50
    g_ops, g_bytes = ref.moe_gmm_flops_bytes(s, tokens,
                                             [256] * 4 + [60] * 40
                                             + [100] * 10)
    c_ops, c_bytes = ref.short_conv_flops_bytes(s, tokens, 4 + 50 * 60)
    a_ops, a_bytes = ref.attention_flops_bytes(s, list(zip(before, after)),
                                               50 * 120000)
    assert a_bytes == 2 * 50 * 120000 * 2048
    least = {name: max(o / 197e12, b / 819e9) for name, (o, b) in (
        ("moe_gmm", (g_ops, g_bytes)), ("short_conv", (c_ops, c_bytes)),
        ("attn", (a_ops, a_bytes)))}
    for slower in (1.0, 3.0):
        t = _trace([("_moe_gmm_kernel.7", 10.2, slower * least["moe_gmm"]),
                    ("_short_conv_kernel.2", 10.5,
                     slower * least["short_conv"]),
                    ("_chunk_kernel.3", 11.0, slower * least["attn"] * 0.75),
                    ("_decode_kernel", 11.5, slower * least["attn"] * 0.25),
                    ("fusion.12", 12.7, 0.01)])
        ctx = _ctx(cell, t, before, after, steps, program)
        for name in ("moe_gmm", "short_conv", "attn"):
            got = cell.reader(name + "_roofline.reason")(ctx)
            assert got == pytest.approx(100.0 / slower), name
    assert cell.reader("decode_rows_mean.reason")(ctx) == 60.0


def test_the_readers_find_nothing_where_the_program_has_no_such_kernel(
        cell):
    """A program from before the kernels existed, a sub-window in which
    nothing advanced: no event, no number, no exception."""
    t = _trace([("fusion.1", 10.5, 0.2)])
    ctx = _ctx(cell, t, [0], [256], program_spans=_spans([256], []))
    for name in ("moe_gmm", "short_conv", "attn"):
        assert cell.reader(name + "_roofline.reason")(ctx) is None
    assert cell.reader("decode_rows_mean.reason")(ctx) is None


# -- a line the cell printed on the chip ------------------------------------------

@pytest.mark.parametrize("traced", [False, True])
def test_check_line_takes_a_line_the_cell_printed_on_the_chip(cell, traced):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "lfm2-reason-backlog.json")
    with open(path) as f:
        recorded = json.load(f)["traced" if traced else "untraced"]
    # two metrics over the program's spans came after these lines were
    # printed; the chip has read them on the doc backlog's cell
    later = {"idle_unattributed_pct.reason", "chunk_dispatch_ms.reason"}
    assert later <= {m["name"] for m in cell.per_layer}
    cell = spec.load_cell(CELL)
    cell.per_layer = [m for m in cell.per_layer if m["name"] not in later]
    line.check_line(recorded, cell, traced)
    assert recorded["correct"] is True and recorded["failed"] == 0
    assert recorded["device"]["kind"] == "TPU v5 lite"
    # the floor a new cell has to meet: a quarter of the chip's memory
    assert recorded["device"]["memory_peak_bytes"] > 0.25 * 16e9
    if traced:
        for name, m in recorded["metrics"].items():
            if "roofline" in name or "mfu" in name:
                assert 0 < m["value"] <= 100, name
        missing = dict(recorded, metrics={
            k: v for k, v in recorded["metrics"].items()
            if k != "short_conv_roofline.reason"})
        with pytest.raises(line.LineError, match="is missing"):
            line.check_line(missing, cell, True)
