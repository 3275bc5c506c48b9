"""Multi-host bootstrap env mapping + local launcher
(reference ``apex/parallel/multiproc.py`` behavior)."""

import pytest

from apex_tpu.parallel import multiproc


@pytest.fixture
def clean_env(monkeypatch):
    for var in ("COORDINATOR_ADDRESS", "MASTER_ADDR", "MASTER_PORT",
                "NUM_PROCESSES", "WORLD_SIZE", "PROCESS_ID", "RANK"):
        monkeypatch.delenv(var, raising=False)


def _capture_initialize(monkeypatch):
    calls = {}

    def fake_init(coordinator_address, num_processes, process_id):
        calls.update(addr=coordinator_address, n=num_processes,
                     pid=process_id)

    import jax
    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    return calls


def test_single_process_is_noop(clean_env, monkeypatch):
    calls = _capture_initialize(monkeypatch)
    assert multiproc.initialize_distributed() == 0
    assert not calls


def test_jax_style_env(clean_env, monkeypatch):
    calls = _capture_initialize(monkeypatch)
    monkeypatch.setenv("COORDINATOR_ADDRESS", "host0:1234")
    monkeypatch.setenv("NUM_PROCESSES", "4")
    monkeypatch.setenv("PROCESS_ID", "3")
    assert multiproc.initialize_distributed() == 3
    assert calls == dict(addr="host0:1234", n=4, pid=3)


def test_torch_style_env_mapped(clean_env, monkeypatch):
    """WORLD_SIZE/RANK/MASTER_ADDR(+PORT) — the reference ecosystem's
    convention (examples/imagenet/main_amp.py:111-123) — maps onto
    jax.distributed.initialize."""
    calls = _capture_initialize(monkeypatch)
    monkeypatch.setenv("MASTER_ADDR", "10.0.0.1")
    monkeypatch.setenv("MASTER_PORT", "2222")
    monkeypatch.setenv("WORLD_SIZE", "2")
    monkeypatch.setenv("RANK", "1")
    assert multiproc.initialize_distributed() == 1
    assert calls == dict(addr="10.0.0.1:2222", n=2, pid=1)


def test_multi_process_without_coordinator_raises(clean_env, monkeypatch):
    _capture_initialize(monkeypatch)
    monkeypatch.setenv("WORLD_SIZE", "2")
    with pytest.raises(RuntimeError, match="coordinator"):
        multiproc.initialize_distributed()


def test_real_two_process_bootstrap(clean_env, tmp_path, monkeypatch):
    """UNMOCKED multi-process bootstrap: the launcher spawns two
    processes whose ``initialize_distributed()`` really runs
    ``jax.distributed.initialize`` (CPU backend), and a cross-process
    allgather proves the distributed runtime is live — the analog of
    the reference's real 2-process NCCL tier
    (``tests/distributed/DDP/ddp_race_condition_test.py``)."""
    import os
    import socket

    # pick a free coordinator port so parallel test runs can't collide
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    repo_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(multiproc.__file__))))
    script = tmp_path / "worker.py"
    script.write_text(
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "from jax.experimental import multihost_utils\n"
        "from apex_tpu.parallel import multiproc\n"
        "pid = multiproc.initialize_distributed()\n"
        "gathered = multihost_utils.process_allgather(\n"
        "    np.asarray([pid], np.int32))\n"
        "with open(f'result_{pid}.txt', 'w') as f:\n"
        "    f.write(f'{jax.process_count()} '\n"
        "            f'{sorted(gathered.ravel().tolist())}')\n")
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("NUM_PROCESSES", "2")
    monkeypatch.setenv("COORDINATOR_ADDRESS", f"localhost:{port}")
    monkeypatch.setenv(
        "PYTHONPATH",
        repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""))
    rc = multiproc.main([str(script)])
    assert rc == 0
    for r in (0, 1):
        # both processes saw the 2-process world AND each other's rank
        assert (tmp_path / f"result_{r}.txt").read_text() == "2 [0, 1]"


def test_launcher_spawns_world_size_processes(clean_env, tmp_path,
                                              monkeypatch):
    """The local launcher forks NUM_PROCESSES copies with PROCESS_ID set
    and logs non-rank0 to PROC_i.log (reference GPU_i.log behavior)."""
    script = tmp_path / "probe.py"
    script.write_text(
        "import os, pathlib\n"
        "pid = os.environ['PROCESS_ID']\n"
        "pathlib.Path(f'rank_{pid}.txt').write_text(\n"
        "    os.environ['NUM_PROCESSES'])\n"
        "print('hello from', pid)\n")
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("NUM_PROCESSES", "2")
    rc = multiproc.main([str(script)])
    assert rc == 0
    assert (tmp_path / "rank_0.txt").read_text() == "2"
    assert (tmp_path / "rank_1.txt").read_text() == "2"
    assert "hello from 1" in (tmp_path / "PROC_1.log").read_text()


def test_launcher_refuses_on_a_tpu_host(clean_env, tmp_path, monkeypatch,
                                        capsys):
    """Local copies share one environment, so on a TPU host each would
    open every chip: the launcher starts none of them and says what it
    is for.  JAX_PLATFORMS=cpu children need no chip and still run."""
    monkeypatch.setattr(multiproc, "_local_tpu_chips", lambda: 4)
    monkeypatch.setattr(
        multiproc.subprocess, "Popen",
        lambda *a, **k: pytest.fail("started a child on a TPU host"))
    monkeypatch.setenv("NUM_PROCESSES", "4")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert multiproc.main([str(tmp_path / "never_run.py")]) == 2
    assert "one process" in capsys.readouterr().err
