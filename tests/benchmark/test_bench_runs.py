"""Whole runs on the CPU at a tiny size, through the command line's
``main`` with only the look for a chip skipped.  The cells are added to
a copy of the benchmark as NEW files and entries: two configurations
(one in GPT-2's key names, one in another dialect with its own program
and reference files), four traffic mixes, a metric.  Then the same runs
with the timed path broken underneath, each of which has to come out as
not correct."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_root  # noqa: E402
from benchmarks import run  # noqa: E402
from benchmarks.harness import line, spec  # noqa: E402

ROOT = tiny_root.ROOT


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    return tiny_root.make(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def added4(tmp_path_factory):
    return tiny_root.make(tmp_path_factory.mktemp("bench4"), chips=4)


def drive(root, capsys, cell, seed, seconds="1"):
    capsys.readouterr()
    assert run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     seconds, "--trace", "0"], root=root,
                    require_chip=False) == 0
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    return last, err


@pytest.mark.parametrize("cell,metrics", [
    ("tiny-train", {"train_tokens_per_s", "setup_s"}),
    ("tiny-chat", {"itl_p50_ms", "setup_s"}),
    ("tiny-backlog", {"serve_tokens_per_s", "setup_s"}),
    # the same three under a configuration that holds none of GPT-2's
    # key names and brings its own program and reference files
    ("hf-train", {"train_tokens_per_s", "setup_s"}),
    ("hf-chat", {"itl_p50_ms", "setup_s"}),
    ("hf-backlog", {"serve_tokens_per_s", "setup_s"}),
])
def test_new_cells_run_with_no_edit_to_a_file_that_was_there(
        added, capsys, cell, metrics):
    root, before = added
    last, err = drive(root, capsys, cell, 2 ** 31 + 5)
    assert last["correct"] is True, err[-2000:]
    assert set(last["metrics"]) == metrics
    assert last["attempted"] > 0 and last["failed"] == 0
    assert all(v["value"] > 0 for v in last["metrics"].values())
    assert list(last)[-1] == "compared" and last["compared"]
    # every number compared stands beside its limit, last on stderr too
    tail = [l for l in err.strip().splitlines() if l.startswith("compared")]
    assert len(tail) == len(last["compared"]) + 1
    assert err.strip().splitlines()[-1].startswith("compared notes")
    # sampled requests are read beside the greedy ones that are compared
    assert ("'sampled_over_top_p'" in err) == cell.endswith("-chat")
    line.check_line(last, spec.load_cell(cell, root), False)
    assert tiny_root.unchanged(before) is None


def test_four_chip_cell_runs_on_four_virtual_devices(added4, capsys):
    last, err = drive(added4[0], capsys, "tiny-train", 11)
    assert last["correct"] is True, err[-2000:]
    assert last["device"]["count"] == 4


def test_the_added_metric_is_found_by_its_name(added):
    import test_bench_trace
    root, _ = added
    cell = spec.load_cell("tiny-train", root)
    assert "steps_per_s.tiny" in [m["name"] for m in cell.per_layer]
    tag = next(t for t in test_bench_trace.FIXTURES if "train-1chip" in t)
    t, _ = test_bench_trace.load(tag)
    got = cell.reader("steps_per_s.tiny")({"trace": t})
    n, lo, hi = t.whole_launches("jit_step")
    assert got == pytest.approx(n / (hi - lo)) and got > 0
    # and every metric the cells list has its reader's file
    for name in ("gpt2m-train-1chip", "gpt2m-train-ddp4",
                 "gpt2xl-chat-open", "gpt2xl-doc-backlog"):
        c = spec.load_cell(name)
        for m in c.per_layer:
            assert callable(c.reader(m["name"])), m["name"]


def test_the_harness_reads_no_models_key_names(added):
    """The runners make the program's configuration through the
    configuration's ``program`` file and ask its reference for the
    vocabulary, the longest row and the weights' deviation; the readers
    take the counts from it.  So no file of the harness names a key of
    GPT-2's ``config.json`` or the program's configuration class, and
    the other dialect's file holds none of those keys."""
    names = ("n_embd", "n_layer", "n_head", "n_inner", "n_positions",
             "initializer_range", "layer_norm_epsilon", "pdrop",
             "GPTConfig")
    files = [os.path.join(ROOT, "benchmarks", "run.py")] + [
        os.path.join(d, f)
        for d, _, fs in os.walk(os.path.join(ROOT, "benchmarks", "harness"))
        for f in fs if f.endswith(".py")]
    assert len(files) > 10
    for path in files:
        with open(path) as fh:
            text = fh.read()
        assert not [n for n in names if n in text], path
    assert not os.path.exists(os.path.join(ROOT, "benchmarks", "harness",
                                           "flops.py"))
    hf = spec.load_cell("hf-train", added[0]).config
    assert not set(hf) & set(tiny_root.TINY_SIZES) - {
        "name", "source", "vocab_size", "reduced", "reference", "program",
        "optimizer", "limits"}
    assert not [k for k in hf if "pdrop" in k]


def test_requests_that_share_a_document_hit_the_servers_prefix_cache(
        added):
    """The tiny shared mix asks each document two or three times, three
    places apart: the server's own counter of prompt tokens found in
    its prefix cache rises through the window, and with a mix that
    shares nothing it stays at nought."""
    import time

    import jax
    from benchmarks.harness import serve
    hits = {}
    for name in ("tiny-shared", "tiny-backlog"):
        cell = spec.load_cell(name, added[0])
        s = serve.Session(cell, 11, 1.0, False, jax.devices()[:1],
                          time.perf_counter())
        m = s.run["marks"]
        hits[name] = (m["close"]["prefix_hit_tokens"]
                      - m["open"]["prefix_hit_tokens"])
        assert s.e2e["failed"] == 0 and s.e2e["attempted"] > 10
        if name == "tiny-shared":
            gaps = s.reference_gaps()
            assert len(gaps) and float(gaps.max()) < 0.004
    assert hits["tiny-backlog"] == 0
    # a document of 40 to 80 ids shares two to five blocks of 16
    assert hits["tiny-shared"] >= 32 * 5


# -- the timed path broken underneath ----------------------------------------

def _unchanged_state(real):
    """A step that returns its state as it got it."""
    def build(cfg, mesh=None, lr=3e-4):
        import jax
        model, opt, step, loss_of = real(cfg, mesh, lr)
        assert mesh is None
        return model, opt, jax.jit(
            lambda p, s, ids: (p, s, loss_of(p, ids))), loss_of
    return build


def _half_batch(real):
    """Half of the batch left out, the mean taken over the rest."""
    def build(cfg, mesh=None, lr=3e-4):
        model, opt, step, loss_of = real(cfg, mesh, lr)
        return model, opt, (lambda p, s, ids: step(
            p, s, ids[:ids.shape[0] // 2])), loss_of
    return build


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
def test_a_broken_step_is_not_correct(added, capsys, monkeypatch, fault):
    import chip_smoke
    monkeypatch.setattr(chip_smoke, "build_trainer",
                        fault(chip_smoke.build_trainer))
    last, err = drive(added[0], capsys, "tiny-train", 11)
    assert last["correct"] is False
    over = [k for k, c in last["compared"].items()
            if c["value"] > c["limit"]]
    assert over and "OVER" in err


def test_the_exchange_between_chips_left_out_is_not_correct(
        added4, capsys, monkeypatch):
    from apex_tpu import parallel
    monkeypatch.setattr(parallel.DistributedDataParallel,
                        "reduce_gradients", lambda self, grads: grads)
    last, _ = drive(added4[0], capsys, "tiny-train", 11)
    assert last["correct"] is False
    assert last["compared"]["grad_norm_gap"]["value"] > 0.3


@pytest.mark.parametrize("cell", ["tiny-chat", "tiny-backlog"])
def test_a_token_altered_where_it_is_produced_is_not_correct(
        added, capsys, monkeypatch, cell):
    from apex_tpu.serving import scheduler
    real = scheduler.Request.record_token
    count = [0]

    def altered(self, token):
        count[0] += 1
        return real(self, (int(token) + 1) % 512 if count[0] % 5 == 0
                    else token)

    monkeypatch.setattr(scheduler.Request, "record_token", altered)
    last, _ = drive(added[0], capsys, cell, 11)
    assert last["correct"] is False
    assert last["compared"]["served_gap_max"]["value"] > 0.1


# -- a profiler that blocks the thread when it stops -------------------------

class _StallingWindow:
    """The profiler as it is at GPT-2 XL's size: ``stop`` blocks the
    one thread that generates and serves."""
    STALL_S = 1.5

    def __init__(self):
        self.started = self.open = False

    def start(self):
        self.started = self.open = True

    def stop(self):
        import time
        self.open = False
        time.sleep(self.STALL_S)


@pytest.mark.parametrize("trace,clean", [
    ({"ends_with_window": True, "seconds": 0.5}, True),
    ({"start_fraction": 0.2, "seconds": 0.5}, False),
], ids=["ends_with_window", "mid_window"])
def test_a_traced_chat_run_does_not_read_the_profilers_stall(
        added, monkeypatch, trace, clean):
    """A traced run's latencies are taken over the requests due before
    the profiler opened, so they are the server's wherever the
    sub-window lies.  With it at the window's end the stop falls after
    the close and they cover nearly all of the window; in mid-window
    they would cover the stretch before it alone (why the chat mix
    says ``ends_with_window``)."""
    import dataclasses
    import time

    import jax
    from benchmarks.harness import serve
    monkeypatch.setattr(serve, "SubWindow", _StallingWindow)
    cell = spec.load_cell("tiny-chat", added[0])
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, trace=trace))
    s = serve.Session(cell, 11, 3.0, True, jax.devices()[:1],
                      time.perf_counter())
    stall_ms = 1e3 * _StallingWindow.STALL_S
    assert s.run["stalls"]["stop_trace_s"] >= _StallingWindow.STALL_S
    assert (s.run["stalls"]["stop_after_close_s"] >= 0) == clean
    assert s.e2e["ttft_p95_ms"] < 0.5 * stall_ms
    assert s.e2e["gen_late_p95_ms"] < 0.5 * stall_ms
    assert s.e2e["failed"] == 0
    covered = len(s.ctx["queue_waits"]) / s.e2e["attempted"]
    assert covered > 0.7 if clean else 0 < covered < 0.4


# -- the command line's refusals ----------------------------------------------

def _cli(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"),
         "--workload", "gpt2m-train-1chip", "--seed", "1", "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_without_a_chip_the_command_exits_non_zero_and_prints_no_result():
    done = _cli(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0 and done.stdout == ""
    assert "no TPU" in done.stderr


def test_with_only_the_benchmarks_files_it_exits_non_zero(tmp_path):
    """A directory that holds ``BENCHMARK.json`` and the files under
    ``paths`` and nothing of the program."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = _cli(str(tmp_path), {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert done.returncode != 0 and done.stdout == ""
