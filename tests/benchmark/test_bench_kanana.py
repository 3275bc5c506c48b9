"""The ``kanana-2-30b-a3b`` configuration and its cell
``kanana2-longdoc-backlog``: the counts by hand, a tiny cell of the
family through the command line's ``main`` on the CPU, the two roofline
readers on a synthetic trace, and the cell's two kernels compiled at its
shapes for a described v5e.  The tiny cell comes in as files and entries
alone, on top of ``tiny_root``'s copy of the benchmark."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_root  # noqa: E402
from benchmarks import run  # noqa: E402
from benchmarks.harness import line, spec  # noqa: E402
from benchmarks.harness.trace import Line, Trace  # noqa: E402

ROOT = tiny_root.ROOT
CELL = "kanana2-longdoc-backlog"


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


# -- the counts by hand ---------------------------------------------------------

def test_parameter_counts_by_hand(cell):
    ref = cell.reference()
    published = dict(cell.config, **cell.config["published"])
    p = ref.params_by_part(published)
    # q 2,048 x 6,144; kv_a 2,048 x 576; kv_b 512 x 8,192; o 4,096 x 2,048
    assert p["attention"] == 12_582_912 + 1_179_648 + 4_194_304 + 8_388_608
    assert round(p["attention"] / 1e6, 2) == 26.35
    assert p["dense_ff"] == 3 * 2048 * 6144
    assert p["expert"] == 3 * 2048 * 768
    assert p["shared_and_router"] == 3 * 2048 * 1536 + 2048 * 128 + 128
    assert round(p["expert_layer"] / 1e6, 1) == 640.0
    assert round(p["embedding_and_head"] / 1e6, 1) == 525.3
    assert round(p["total"] / 1e9, 2) == 30.67
    # as run: the dense layer and six expert layers
    assert round(ref.total_params(cell.config) / 1e9, 2) == 4.43
    # what the table draws is what is counted, the norms' weights aside
    drawn = sum(int(np.prod(s)) for s, kind in
                ref.param_table(cell.config).values() if kind != "ones")
    assert drawn == ref.total_params(cell.config)
    # 64.4M in the products of one token in one expert layer
    active = ref.active_matmul_params(published)
    assert active == 48 * p["attention"] + p["dense_ff"] + 47 * (
        6 * p["expert"] + 3 * 2048 * 1536 + 2048 * 128)
    assert round((p["attention"] + 6 * p["expert"] + 3 * 2048 * 1536
                  + 2048 * 128) / 1e6, 1) == 64.4


def test_cache_bytes_by_hand(cell):
    ref = cell.reference()
    assert ref.cache_bytes_per_token(cell.config) == 7 * 576 * 2 == 8064
    assert ref.cache_bytes_per_token(cell.config, stored=True) \
        == 7 * 640 * 2 == 8960
    stated = cell.config["cache"]["bytes_a_token_7_layers"]
    assert stated == {"kept": 8064, "stored": 8960}


def test_the_configuration_holds_the_published_widths(cell):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    ours = cell.config
    differ = {k for k, v in row["config"].items() if ours.get(k) != v}
    assert differ == {"num_hidden_layers"} == set(ours["reduced"])
    assert ours["num_hidden_layers"] == 7
    assert ours["published"] == {"num_hidden_layers": 48}
    assert ours["source"] == row["source_url"]
    assert ours["reference_longest_row"] == cell.traffic["max_total"] \
        == cell.traffic["server"]["max_context"]


def test_forward_flops_at_two_positions_by_hand(cell):
    ref, s = cell.reference(), cell.config
    per_token = 2 * ref.active_matmul_params(s)
    head = 2 * 2048 * 128256
    per_key = 7 * 2 * 32 * (192 + 128)
    # the first token attends itself alone
    assert ref.forward_flops_at(s, 0, 1) == per_token + per_key + head
    # a decode step at position 12,000: 12,001 keys
    assert ref.forward_flops_at(s, 12000, 12001) \
        == per_token + per_key * 12001 + head
    # a chunk of 256 from 8,192: keys 8,193 .. 8,448
    keys = sum(range(8193, 8449))
    assert ref.forward_flops_at(s, 8192, 8448) \
        == 256 * per_token + per_key * keys + head
    assert ref.forward_flops_at(s, 5, 5) == 0


def test_every_metric_of_the_cell_has_its_reader(cell):
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == 8 and all(n.endswith(".longdoc") for n in names)
    for n in names:
        assert callable(cell.reader(n)), n


def test_the_mix_is_the_issues(cell):
    mix = cell.traffic
    assert (mix["loop"], mix["clients"]) == ("closed", 12)
    # ISSUE 27's 8,192..16,384 with the one narrowing it allows (the
    # mix's ``prompt_why`` gives the spread that called for it)
    assert mix["prompt"] == {"dist": "uniform", "min": 10240, "max": 14336}
    assert "5.4%" in mix["prompt_why"]
    assert mix["output"] == {"dist": "uniform", "min": 64, "max": 256}
    assert mix["max_total"] == 16640 and "shared" not in mix
    assert mix["server"] == {"max_batch_size": 8, "max_context": 16640}
    assert mix["check"] == {"requests": 3, "sampled_requests": 2,
                            "rows_per_block": 1}
    from benchmarks.harness import traffic
    plan = traffic.plan_requests(mix, 2 ** 31 + 3, 24, 128256)
    assert all(10240 <= len(p.prompt) <= 14336 for p in plan)
    assert all(len(p.prompt) + p.max_new <= 16640 for p in plan)
    assert sum(p.greedy for p in plan) == 3
    assert max(max(p.prompt) for p in plan) > 100000      # whole vocabulary


# -- a tiny cell of the family through the command line's main ------------------

TINY = {
    "name": "kanana-tiny", "source": "a test's own sizes",
    "model_type": "deepseek_v3", "attention_bias": False,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "q_lora_rank": None, "rope_scaling": None, "n_group": 1,
    "topk_group": 1, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "moe_layer_freq": 1, "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 16, "qk_head_dim": 32, "v_head_dim": 16,
    "rope_theta": 1000000, "rope_interleave": True, "rms_norm_eps": 1e-06,
    "routed_scaling_factor": 2.448, "norm_topk_prob": True,
    "max_position_embeddings": 256, "reduced": [],
    "assumed": {"initializer_range": 0.02}, "reference_longest_row": 128,
    "reference": "benchmarks/configs/kanana-tiny.reference.py",
    "program": "benchmarks/configs/deepseek_v3.program.py",
    # from readings at this size on the CPU (test_tiny_limit_readings)
    "limits": {"serve": {"served_gap_max": 0.004}},
}

TINY_MIX = {
    "runner": "serve", "loop": "closed", "clients": 6,
    "prompt": {"dist": "uniform", "min": 40, "max": 100},
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
    configuration of this family with a mix and a cell, as new files
    and entries."""
    root, before = tiny_root.make(tmp_path_factory.mktemp("kanana"))
    b = os.path.join(root, "benchmarks")
    with open(os.path.join(b, "configs", "kanana-tiny.json"), "x") as f:
        json.dump(TINY, f)
    with open(os.path.join(b, "configs",
                           "kanana-2-30b-a3b.reference.py")) as f:
        text = f.read()
    with open(os.path.join(b, "configs", "kanana-tiny.reference.py"),
              "x") as f:
        f.write(text)
    with open(os.path.join(b, "workloads", "tiny-longdoc.json"), "x") as f:
        json.dump(TINY_MIX, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "kanana-tiny", "source": "a test", "reduced": [],
        "file": "benchmarks/configs/kanana-tiny.json", "why": "a test"})
    bench["workloads"].append({
        "name": "kanana-tiny-longdoc", "config": "kanana-tiny",
        "traffic": "tiny-longdoc", "chips": 1, "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("kanana-tiny-longdoc")
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root, before


def drive(root, capsys, seed):
    capsys.readouterr()
    assert run.main(["--workload", "kanana-tiny-longdoc", "--seed",
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
    line.check_line(last, spec.load_cell("kanana-tiny-longdoc", root),
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


# -- which positions the reference judges ---------------------------------------

MARGIN_SIZES = dict(
    vocab_size=2048, hidden_size=128, num_hidden_layers=3,
    num_attention_heads=4, intermediate_size=256, moe_intermediate_size=64,
    n_routed_experts=32, n_shared_experts=1, num_experts_per_tok=4,
    first_k_dense_replace=1, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=16, v_head_dim=16, rope_theta=1e6, rms_norm_eps=1e-6,
    routed_scaling_factor=2.448, norm_topk_prob=True,
    assumed={"initializer_range": 0.02}, reference_longest_row=256)


@pytest.fixture(scope="module")
def margins():
    """Seeded bfloat16 weights at a small size with 32 experts, rows of
    ids, and what the float32 reference says of every position with
    nothing masked: the least margin, ``token_gaps``, and how far the
    token each control puts first lies below float32's best (as
    ``harness/serve.py::served_gaps`` reads a control)."""
    from benchmarks.harness import weights
    from benchmarks.reference import deepseek_v3 as ref
    params = weights.make_params(ref.param_table(MARGIN_SIZES), 3,
                                 jnp.bfloat16, 0.02)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 2048, (3, 256)),
                      jnp.int32)
    least = np.asarray(jax.jit(lambda p, i: ref.routing_margins(
        p, i, MARGIN_SIZES))(params, ids)).min(0)[:, :-1]
    whole = [np.asarray(a) for a in _gaps(ref, MARGIN_SIZES)(params, ids)]
    firsts = {p: _gaps(ref, MARGIN_SIZES, p)(params, ids)[2]
              for p in ("bfloat16", "fp8")}
    wide = {p: np.asarray(_gap_of(ref, MARGIN_SIZES)(params, ids, t))
            for p, t in firsts.items()}
    return ref, params, ids, least, whole, firsts, wide


def _gaps(ref, sizes, precision="float32"):
    return jax.jit(lambda p, i: ref.token_gaps(p, i, None, sizes, precision))


def _gap_of(ref, sizes):
    return jax.jit(lambda p, i, t: ref.logit_at(p, i, t, sizes))


def test_a_margin_is_the_kth_score_less_the_next(margins):
    ref, least = margins[0], margins[3]
    assert least.shape == (3, 255) and (least >= 0).all()
    # 4 of 32 scores: the k-th and the next lie about 1/200 apart
    assert 0.001 < np.median(least) < 0.01
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 5, 8)),
                    jnp.float32)
    p = {"router": jnp.eye(8), "e_score_correction_bias": jnp.zeros(8)}
    sizes = dict(MARGIN_SIZES, num_experts_per_tok=3)
    _, chosen, margin = ref.routing_weights(x, p, sizes, lambda a: a)
    s = np.sort(np.asarray(jax.nn.sigmoid(x)), -1)
    np.testing.assert_allclose(margin, s[..., -3] - s[..., -4], rtol=1e-6)
    assert chosen.shape == (1, 5, 3)


@pytest.mark.parametrize("floor", [None, 0.002, 1.0])
def test_a_gap_is_nought_where_the_routing_is_not_decided(margins, floor):
    ref, params, ids, least, whole, _, _ = margins
    sizes = dict(MARGIN_SIZES, decided_margin=floor)
    best, gap, first = _gaps(ref, sizes)(params, ids)
    kept = np.ones_like(least, bool) if floor is None else least > floor
    np.testing.assert_array_equal(np.asarray(gap),
                                  np.where(kept, whole[1], 0))
    # what is not a gap is not masked
    np.testing.assert_array_equal(np.asarray(best), whole[0])
    np.testing.assert_array_equal(np.asarray(first), whole[2])
    if floor == 0.002:
        assert 0.3 < kept.mean() < 0.7
    else:
        assert kept.mean() == (floor is None)


def test_the_floor_takes_out_bfloat16s_other_choices_and_not_fp8s(margins):
    """What ``decided_margin`` is for: judged everywhere, the widest gap
    of the reference's own bfloat16 control is an expert chosen
    otherwise; judged where float32's routing is decided it is rounding,
    while fp8 still reads nearly as wide as it did."""
    ref, params, ids, least, _, firsts, wide = margins
    kept = least > 0.002
    sizes = dict(MARGIN_SIZES, decided_margin=0.002)
    judged = {p: np.asarray(_gap_of(ref, sizes)(params, ids, t))
              for p, t in firsts.items()}
    for p in wide:
        np.testing.assert_array_equal(judged[p], np.where(kept, wide[p], 0))
    print("widest gap everywhere", {p: g.max() for p, g in wide.items()},
          "where decided", {p: g.max() for p, g in judged.items()})
    assert wide["bfloat16"].max() > 10 * judged["bfloat16"].max()
    assert judged["fp8"].max() > 0.5 * wide["fp8"].max()
    assert judged["fp8"].max() > 20 * judged["bfloat16"].max()


# -- the two roofline readers on a synthetic trace ------------------------------

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
            "run": {"steps": list(steps),
                    "sub": {"open": {"at": 10.0, "cached": before,
                                     "families": families[0]},
                            "close": {"at": 13.0, "cached": after,
                                      "families": families[1]}}}}


def test_the_roofline_readers_stay_under_100_at_the_least_time(cell):
    """Kernels that took exactly the least time the counts allow read
    100; any real kernel is slower and reads under it.  Ten chunks of
    256 tokens at positions 8,192 on and 40 decode launches over 8
    slots of 12,000 cached tokens."""
    ref, s = cell.reference(), cell.config
    before, after = [8192, 12000], [8192 + 2560, 12040]
    fams = ({"chunk_prefill_stoch[256]": (100, 1), "decode_stoch": (50, 1)},
            {"chunk_prefill_stoch[256]": (110, 1), "decode_stoch": (90, 1),
             "verify_sampled[5]": (0, 0)})
    steps = [(10.0 + 0.05 * i, 10.01 + 0.05 * i, 1, "decode", 96000, 5)
             for i in range(40)]
    ops, nbytes = ref.moe_gmm_flops_bytes(s, 2600, 10, 40)
    assert nbytes == 6 * 3 * 2048 * 768 * 2 * (10 * 128 + 40 * 6)
    assert ops == 2 * 2600 * 6 * 3 * 2048 * 768 * 6
    least_gmm = max(ops / 197e12, nbytes / 819e9)
    a_ops, a_bytes = ref.latent_attention_flops_bytes(
        s, list(zip(before, after)), 40 * 96000)
    assert a_bytes == 40 * 96000 * 8064
    least_attn = max(a_ops / 197e12, a_bytes / 819e9)
    for slower in (1.0, 3.0):
        t = _trace([("_moe_gmm_kernel.7", 10.2, slower * least_gmm),
                    ("_latent_chunk_kernel.3", 11.0,
                     slower * least_attn * 0.75),
                    ("_latent_decode_kernel", 12.0,
                     slower * least_attn * 0.25),
                    ("fusion.12", 12.5, 0.01)])
        ctx = _ctx(cell, t, before, after, fams, steps)
        gmm = cell.reader("moe_gmm_roofline.longdoc")(ctx)
        attn = cell.reader("latent_attn_roofline.longdoc")(ctx)
        assert gmm == pytest.approx(100.0 / slower)
        assert attn == pytest.approx(100.0 / slower)
        assert 0 < gmm <= 100 and 0 < attn <= 100


def test_the_roofline_readers_find_nothing_where_the_kernels_are_not(cell):
    """A program from before the kernels existed: no event, no number,
    no exception."""
    t = _trace([("fusion.1", 10.5, 0.2)])
    ctx = _ctx(cell, t, [0], [256], ({}, {"chunk_prefill[256]": (1, 1)}))
    assert cell.reader("moe_gmm_roofline.longdoc")(ctx) is None
    assert cell.reader("latent_attn_roofline.longdoc")(ctx) is None


# -- the cell's kernels compiled at its shapes for a described v5e --------------

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
def no_cache():
    # a compile for a described chip cannot be read back from the cache
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("slots,rows,name", [
    (8, 1, "_latent_decode_kernel"), (8, 5, "_latent_verify_kernel"),
    (1, 256, "_latent_chunk_kernel")])
def test_latent_kernel_at_the_cells_shapes(one_chip, no_cache, cell, slots,
                                           rows, name):
    """32 heads' absorbed queries of 640 over a pool of 8 slots of
    16,640 positions in 7 layers, read through the block table."""
    from apex_tpu.ops.decode_attention import paged_attention
    srv = cell.traffic["server"]
    nb = -(-srv["max_context"] // 16)
    pool = jax.ShapeDtypeStruct(
        (7, (srv["max_batch_size"] * nb + 1) * 16, 640), jnp.bfloat16,
        sharding=one_chip)
    q = jax.ShapeDtypeStruct((slots, rows, 32, 640), jnp.bfloat16,
                             sharding=one_chip)
    ints = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in ((slots, nb), (slots,))]
    text = jax.jit(lambda q, p, t, s: paged_attention(
        q, p, 3, t, s, block_size=16, scale=192 ** -0.5, latent_value=512,
        interpret=False)).lower(q, pool, *ints).compile().as_text()
    assert name in text


@pytest.mark.parametrize("rows", [256 * 6, 8 * 6, 8 * 5 * 6])
@pytest.mark.parametrize("k,n", [(2048, 768), (768, 2048)])
def test_grouped_product_at_the_cells_shapes(one_chip, no_cache, rows, k, n):
    """A chunk's, a decode step's and a verify step's (token, expert)
    pairs against 128 experts' gate or up, and down, matrices."""
    from apex_tpu.ops.grouped_matmul import KERNEL_NAME, grouped_matmul
    lhs = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((128, k, n), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((128,), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda a, b, s: grouped_matmul(
        a, b, s, use_pallas=True, interpret=False)).lower(
            lhs, rhs, sizes).compile().as_text()
    assert KERNEL_NAME in text
