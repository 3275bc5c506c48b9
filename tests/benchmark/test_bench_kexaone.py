"""The ``k-exaone-236b-a23b`` configuration and its cell
``kexaone-mixedlen-backlog``: the file against the catalog's keys, the
counts by hand and against a spelt-out loop, a tiny cell of the family
through the command line's ``main`` on the CPU, the readers on a
synthetic trace, and ``check_line`` on a line the cell printed on the
chip.  The tiny cell comes in as files and entries alone, on top of
``tiny_root``'s copy of the benchmark."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_root  # noqa: E402
from benchmarks import run  # noqa: E402
from benchmarks.harness import line, spec  # noqa: E402
from benchmarks.harness.trace import Line, Trace  # noqa: E402

ROOT = tiny_root.ROOT
CELL = "kexaone-mixedlen-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size",
           "num_nextn_predict_layers"}


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


# -- the file against the catalog ------------------------------------------------

def test_the_configuration_holds_the_published_widths(cell):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    ours = cell.config
    differ = {k for k, v in row["config"].items() if ours.get(k) != v}
    assert differ == REDUCED == set(ours["reduced"])
    assert ours["published"] == {k: row["config"][k] for k in REDUCED} == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600,
        "num_nextn_predict_layers": 1}
    assert (ours["num_hidden_layers"], ours["num_experts"],
            ours["vocab_size"], ours["num_nextn_predict_layers"]) \
        == (8, 8, 19200, 0)
    # every width as published
    assert (ours["hidden_size"], ours["num_attention_heads"],
            ours["num_key_value_heads"], ours["head_dim"],
            ours["intermediate_size"], ours["moe_intermediate_size"],
            ours["num_experts_per_tok"], ours["sliding_window"],
            ours["sliding_window_pattern"]) \
        == (6144, 64, 8, 128, 18432, 2048, 8, 128, "LLLG")
    assert ours["source"] == row["source_url"]
    # the floors: two whole periods, 7 layers after the dense one, 8
    # experts a layer, an eighth of the vocabulary
    assert ours["layer_types"][:8] == ["sliding_attention"] * 3 \
        + ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert ours["vocab_size"] * 8 == ours["published"]["vocab_size"]
    for key in ("deployment", "cut_why", "assumed", "assumed_why",
                "departures", "precision", "cache", "limits", "limits_why",
                "decided_margin"):
        assert ours[key], key
    assert "16 chips" in ours["deployment"] and "six" in ours["deployment"]
    assert set(ours["assumed"]) >= {"initializer_range", "qk_norm",
                                    "rope_layers", "norm_position"}
    assert ours["reference_longest_row"] == cell.traffic["max_total"] \
        == cell.traffic["server"]["max_context"] == 32768
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == cell.config_name)
    assert set(entry["reduced"]) == REDUCED


def test_the_program_file_maps_the_keys_and_refuses_what_it_cannot(cell):
    from apex_tpu import models
    cfg = cell.model_config(models)
    assert (cfg.num_experts, cfg.held, cfg.vocab_size,
            cfg.num_hidden_layers) == (128, (0, 8), 19200, 8)
    assert cfg.layer_windows() == (128, 128, 128, None) * 2
    row = cfg.cache_row()
    assert (row.groups, row.group_width, row.heads_per_group) == (8, 256, 8)
    program = spec.load_module(os.path.join(ROOT, cell.config["program"]))
    for key, other in (("n_group", 2), ("scoring_func", "softmax"),
                       ("num_nextn_predict_layers", 1),
                       ("rope_parameters", {"rope_type": "yarn",
                                            "rope_theta": 1e6})):
        with pytest.raises(SystemExit, match="cannot express"):
            program.model_config(models, dict(cell.config, **{key: other}))
    with pytest.raises(SystemExit, match="disagree"):
        program.model_config(models, dict(
            cell.config, mlp_layer_types=["sparse"] * 48))

    class Parent:        # a checkout from before the family existed
        pass

    with pytest.raises(SystemExit, match="no exaone_moe family"):
        program.model_config(Parent, cell.config)


# -- the counts by hand ----------------------------------------------------------

def test_parameter_counts_by_hand(cell):
    ref, s = cell.reference(), cell.config
    p = ref.params_by_part(s)
    # q and o 6,144 x 8,192 each, k and v 6,144 x 1,024 each
    assert p["attention"] == 2 * 50_331_648 + 2 * 6_291_456
    assert round(p["attention"] / 1e6, 2) == 113.25
    assert p["dense_ff"] == 3 * 6144 * 18432
    assert p["expert"] == 3 * 6144 * 2048
    assert round(p["expert"] / 1e6, 2) == 37.75
    assert p["shared_and_router"] == 3 * 6144 * 2048 + 6144 * 128 + 128
    assert round((p["attention"] + p["dense_ff"]) / 1e6, 1) == 453.0
    assert round(p["expert_layer"] / 1e6, 1) == 453.8
    assert round(p["embedding_and_head"] / 1e6, 1) == 235.9
    # ISSUE 31's 3,865.5M is the sum of the rounded parts
    assert round(453.0 + 7 * 453.8 + 235.9, 1) == 3865.5
    assert p["total"] == 3_865_314_176
    # what the table draws is what is counted, the norms' weights aside
    drawn = sum(int(np.prod(shape)) for shape, kind in
                ref.param_table(s).values() if kind != "ones")
    assert drawn == ref.total_params(s)
    assert round(drawn * 2 / 1e9, 2) == 7.73
    # the model as published: 236.6B
    whole = dict(s, **s["published"])
    assert round(ref.total_params(whole) / 1e9, 1) == 236.6
    assert round((ref.params_by_part(whole)["expert_layer"]
                  - 128 * p["expert"]) / 1e6, 1) == 151.8
    # half a held expert a token and layer
    assert ref.held_experts_a_token(s) == 0.5
    assert ref.active_matmul_params(s) == 8 * p["attention"] \
        + p["dense_ff"] + 7 * (0.5 * p["expert"] + 3 * 6144 * 2048
                               + 6144 * 128)
    assert ref.row_bytes(s) == 4096
    stated = s["cache"]
    assert stated["full_attention"]["bytes_16_slots_of_32768"] \
        == 2 * 16 * 32768 * 4096
    assert stated["sliding_attention"]["bytes_16_slots"] \
        == 6 * 16 * 512 * 4096
    assert stated["sliding_attention"]["ring_rows"] == 512


TINY_COUNTS = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_experts": 2, "published": {"num_experts": 8},
    "num_shared_experts": 1, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "num_hidden_layers": 8, "vocab_size": 211,
    "sliding_window": 8,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 3}


@pytest.mark.parametrize("first,last", [(0, 1), (0, 5), (3, 8), (5, 21),
                                        (40, 41), (17, 17)])
def test_the_counts_against_a_spelt_out_loop(cell, first, last):
    """Token by token and key by key at a tiny size: every query head's
    score and value row for each key a token sees, the whole past in the
    2 full layers and the last 8 keys in the 6 sliding ones; the
    products a token goes through; the head once a span."""
    ref, s = cell.reference(), TINY_COUNTS
    full = window = 0
    for p in range(first, last):
        for key in range(p + 1):
            full += 2 * 2 * 4 * 16 * 2                  # 2 full layers
            if p - key < 8:
                window += 2 * 2 * 4 * 16 * 6            # 6 sliding layers
    assert ref.attention_flops(s, first, last) == (full, window)
    attention = 64 * 16 * 2 * (4 + 2)
    expert = 3 * 64 * 32
    a_token = 8 * attention + 3 * 64 * 96 + 7 * (
        2 * 2 / 8 * expert + expert + 64 * 8)
    assert ref.active_matmul_params(s) == a_token
    n = last - first
    assert ref.forward_flops_at(s, first, last) == (
        2 * a_token * n + full + window + 2 * 64 * 211 if n else 0)
    spans = [(first, last), (2, 2), (0, 3)]
    extra = ref.attention_flops(s, 0, 3)
    assert ref.full_attention_flops_bytes(s, spans, 100) \
        == (full + extra[0], 2 * 100 * 2 * 32 * 2)
    assert ref.window_attention_flops_bytes(s, spans, 50) \
        == (window + extra[1], 6 * 50 * 2 * 32 * 2)
    # 10 tokens through half a held expert of 7 layers; 3 chunk launches
    # read both held experts, 4 step launches half of one
    assert ref.moe_gmm_flops_bytes(s, 10, 3, 4) == (
        2 * 10 * 0.5 * expert * 7, 7 * expert * 2 * (3 * 2 + 4 * 0.5))


def test_forward_flops_at_the_cells_sizes_by_hand(cell):
    ref, s = cell.reference(), cell.config
    per_token = 2 * ref.active_matmul_params(s)
    head = 2 * 6144 * 19200
    per_key = 2 * 64 * 256
    # a decode step at position 12,000: 12,001 keys in the 2 full
    # layers, 128 in the 6 sliding ones
    assert ref.forward_flops_at(s, 12000, 12001) == per_token + head \
        + per_key * (2 * 12001 + 6 * 128)
    # a chunk of 256 from 8,192
    assert ref.forward_flops_at(s, 8192, 8448) == 256 * per_token + head \
        + per_key * (2 * sum(range(8193, 8449)) + 6 * 128 * 256)
    # ISSUE 31's "about 0.96 TFLOP" a chunk launch counts every expert
    # held as read; the products of the tokens' own routes are less
    assert 0.5e12 < ref.forward_flops_at(s, 8192, 8448) < 1.0e12


def test_every_metric_of_the_cell_has_its_reader(cell):
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    assert sorted(names) == sorted(n + ".mixedlen" for n in (
        "step_mfu", "full_attn_roofline", "window_attn_roofline",
        "moe_gmm_roofline", "chunk_program_ms", "decode_program_ms",
        "device_idle_pct", "step_idle_ms", "itl_p50_ms", "ttft_p50_ms",
        "kv_blocks_used_pct", "window_rows_dropped_pct"))
    for n in names:
        assert callable(cell.reader(n)), n


def test_the_mix_is_the_issues(cell):
    mix = cell.traffic
    assert (mix["loop"], mix["clients"], mix["stratum"]) == ("closed", 24,
                                                             24)
    assert mix["prompt"]["dist"] == mix["output"]["dist"] == "lognormal"
    assert (mix["prompt"]["median"], mix["prompt"]["min"],
            mix["prompt"]["max"]) == (2048, 128, 30720)
    assert mix["prompt"]["sigma"] in (1.2, 1.0)     # the one narrowing
    assert mix["output"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.7, "min": 32, "max": 1024}
    assert mix["sampling"] == {"temperature": 0.8, "top_p": 0.95}
    assert mix["greedy_share"] == 0.125
    assert mix["max_total"] == 32768 and "shared" not in mix
    assert mix["server"] == {"max_batch_size": 16, "max_context": 32768}
    assert (mix["fill_s"], mix["planned_requests"]) == (12.0, 600)
    assert mix["check"]["requests"] == 3
    assert mix["trace"] == {"ends_with_window": True, "seconds": 3.0}
    from benchmarks.harness import traffic
    plan = traffic.plan_requests(mix, 2 ** 31 + 3, 600, 19200)
    lengths = np.asarray([len(p.prompt) for p in plan])
    assert lengths.min() >= 128 and lengths.max() <= 30720
    assert all(len(p.prompt) + p.max_new <= 32768 for p in plan)
    assert all(32 <= p.max_new <= 1024 for p in plan)
    assert sum(p.greedy for p in plan) == 75
    assert max(max(p.prompt) for p in plan) < 19200      # the slice held
    assert 3000 < lengths.mean() < 5000
    assert 0.02 < (lengths > 16384).mean() < 0.06        # one in 25


# -- a tiny cell of the family through the command line's main ------------------

TINY = {
    "name": "kexaone-tiny", "source": "a test's own sizes",
    "model_type": "exaone_moe", "hidden_act": "silu",
    "tie_word_embeddings": False, "n_group": 1, "topk_group": 1,
    "scoring_func": "sigmoid", "num_nextn_predict_layers": 0,
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 4, "published": {"num_experts": 8},
    "num_shared_experts": 1, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "sliding_window": 8,
    "sliding_window_pattern": "LLLG",
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
    "sliding_windows": [8, 8, 8, 0] * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "rms_norm_eps": 1e-05, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "max_position_embeddings": 256, "reduced": [],
    "assumed": {"initializer_range": 0.02}, "reference_longest_row": 128,
    "cache": {"sliding_attention": {"ring_rows": 32}},
    "reference": "benchmarks/configs/kexaone-tiny.reference.py",
    "program": "benchmarks/configs/exaone_moe.program.py",
    # from readings at this size on the CPU
    # (tests/benchmark/control_readings.py): the program 0.0020 and
    # 0.0041 on two seeds, the token fp8 puts first 0.045 and 0.046
    "limits": {"serve": {"served_gap_max": 0.015}},
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
    "check": {"requests": 4, "sampled_requests": 2, "rows_per_block": 1},
    "trace": {"ends_with_window": True, "seconds": 0.5}}


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """``tiny_root``'s copy of the benchmark, and on top of it a tiny
    configuration of this family (half of its 8 experts held) with a
    mix and a cell, as new files and entries."""
    root, before = tiny_root.make(tmp_path_factory.mktemp("kexaone"))
    b = os.path.join(root, "benchmarks")
    with open(os.path.join(b, "configs", "kexaone-tiny.json"), "x") as f:
        json.dump(TINY, f)
    with open(os.path.join(b, "configs",
                           "k-exaone-236b-a23b.reference.py")) as f:
        text = f.read()
    with open(os.path.join(b, "configs", "kexaone-tiny.reference.py"),
              "x") as f:
        f.write(text)
    with open(os.path.join(b, "workloads", "tiny-mixedlen.json"), "x") as f:
        json.dump(TINY_MIX, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "kexaone-tiny", "source": "a test", "reduced": [],
        "file": "benchmarks/configs/kexaone-tiny.json", "why": "a test"})
    bench["workloads"].append({
        "name": "kexaone-tiny-mixedlen", "config": "kexaone-tiny",
        "traffic": "tiny-mixedlen", "chips": 1, "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("kexaone-tiny-mixedlen")
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root, before


def drive(root, capsys, seed):
    capsys.readouterr()
    assert run.main(["--workload", "kexaone-tiny-mixedlen", "--seed",
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
    assert "'sampled_over_top_p'" in err
    for family in ("chunk_prefill_stoch", "decode_stoch", "verify_stoch"):
        assert family in err          # the warm-up found every program
    # no prefix hit is taken for a model with window layers
    assert "found in the prefix cache 0 and not 0" in err
    line.check_line(last, spec.load_cell("kexaone-tiny-mixedlen", root),
                    False)
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


def _ctx(cell, trace, before, after, families, steps=()):
    return {"trace": trace, "ref": cell.reference(), "sizes": cell.config,
            "device_kind": "TPU v5 lite", "chips": 1,
            "run": {"steps": list(steps), "slots": 16,
                    "sub": {"open": {"at": 10.0, "cached": before,
                                     "families": families[0]},
                            "close": {"at": 13.0, "cached": after,
                                      "families": families[1]}}}}


def test_the_roofline_readers_stay_under_100_at_the_least_time(cell):
    """Kernels that took exactly the least time the counts allow read
    100; any real kernel is slower and reads under it.  Ten chunks of
    256 tokens at positions 8,192 on and 40 decode launches over 16
    slots of 4,000 cached tokens."""
    ref, s = cell.reference(), cell.config
    before, after = [8192, 4000], [8192 + 2560, 4040]
    fams = ({"chunk_prefill_stoch[256]": (100, 1), "decode_stoch": (50, 1)},
            {"chunk_prefill_stoch[256]": (110, 1), "decode_stoch": (90, 1),
             "verify_sampled[5]": (0, 0)})
    steps = [(10.0 + 0.05 * i, 10.01 + 0.05 * i, 16, "decode", 64000, 5)
             for i in range(40)]
    ops, nbytes = ref.moe_gmm_flops_bytes(s, 2600, 10, 40)
    assert nbytes == 7 * 3 * 6144 * 2048 * 2 * (10 * 8 + 40 * 0.5)
    assert ops == 2 * 2600 * 0.5 * 3 * 6144 * 2048 * 7
    least_gmm = max(ops / 197e12, nbytes / 819e9)
    spans = list(zip(before, after))
    f_ops, f_bytes = ref.full_attention_flops_bytes(s, spans, 40 * 64000)
    assert f_bytes == 2 * 40 * 64000 * 4096
    w_ops, w_bytes = ref.window_attention_flops_bytes(s, spans,
                                                      40 * 16 * 128)
    assert w_bytes == 6 * 40 * 16 * 128 * 4096
    least_full = max(f_ops / 197e12, f_bytes / 819e9)
    least_window = max(w_ops / 197e12, w_bytes / 819e9)
    for slower in (1.0, 3.0):
        t = _trace([("_moe_gmm_kernel.7", 10.2, slower * least_gmm),
                    ("_chunk_kernel.3", 11.0, slower * least_full * 0.75),
                    ("_decode_kernel", 11.5, slower * least_full * 0.25),
                    ("_window_chunk_kernel.3", 12.0,
                     slower * least_window * 0.5),
                    ("_window_decode_kernel.9", 12.3,
                     slower * least_window * 0.5),
                    ("_latent_decode_kernel", 12.6, 0.01),
                    ("fusion.12", 12.7, 0.01)])
        ctx = _ctx(cell, t, before, after, fams, steps)
        for name in ("moe_gmm_roofline", "full_attn_roofline",
                     "window_attn_roofline"):
            got = cell.reader(name + ".mixedlen")(ctx)
            assert got == pytest.approx(100.0 / slower), name
    # of 10,752 and 4,040 cached rows a window layer, the rings hold 512
    # each
    assert cell.reader("window_rows_dropped_pct.mixedlen")(ctx) \
        == pytest.approx(100.0 * (10752 + 4040 - 2 * 512) / (10752 + 4040))


def test_the_readers_find_nothing_where_the_program_has_no_such_kernel(
        cell):
    """A program from before the kernels existed, a sub-window in which
    nothing advanced: no event, no number, no exception."""
    t = _trace([("fusion.1", 10.5, 0.2)])
    ctx = _ctx(cell, t, [0], [256], ({}, {"chunk_prefill[256]": (1, 1)}))
    for name in ("moe_gmm_roofline", "full_attn_roofline",
                 "window_attn_roofline"):
        assert cell.reader(name + ".mixedlen")(ctx) is None
    idle = _ctx(cell, t, [300], [300], ({}, {}))
    assert cell.reader("window_rows_dropped_pct.mixedlen")(idle) is None


# -- a line the cell printed on the chip ------------------------------------------

@pytest.mark.parametrize("traced", [False, True])
def test_check_line_takes_a_line_the_cell_printed_on_the_chip(cell, traced):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "kexaone-mixedlen-backlog.json")
    with open(path) as f:
        recorded = json.load(f)["traced" if traced else "untraced"]
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
            if k != "window_attn_roofline.mixedlen"})
        with pytest.raises(line.LineError, match="is missing"):
            line.check_line(missing, cell, True)
