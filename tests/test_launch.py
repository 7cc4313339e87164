"""Launcher / spawn / elastic: multi-process on one box (SURVEY §4.2)."""

import os
import subprocess
import sys
import textwrap
import time

import pytest

from paddle_tpu.distributed.fleet.elastic import ElasticManager, ElasticStatus
from paddle_tpu.distributed.store import TCPStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env():
    """Subprocess env: plain CPU jax."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_launch_two_ranks_rendezvous(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys, struct
        sys.path.insert(0, {REPO!r})
        from paddle_tpu.distributed.store import TCPStore
        rank = int(os.environ["PADDLE_TRAINER_ID"])
        world = int(os.environ["PADDLE_TRAINERS_NUM"])
        host, port = os.environ["PADDLE_MASTER"].split(":")
        store = TCPStore(host, int(port), is_master=False, world_size=world)
        store.set(f"rank{{rank}}", str(rank))
        store.barrier(tag="t")
        for r in range(world):
            assert store.get(f"rank{{r}}") is not None
        print("RANK", rank, "OK")
    """))
    log_dir = str(tmp_path / "logs")
    rc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", log_dir, str(script)],
        cwd=REPO, capture_output=True, timeout=120, env=_cpu_env())
    assert rc.returncode == 0, rc.stderr.decode()
    for r in range(2):
        with open(os.path.join(log_dir, f"workerlog.{r}")) as f:
            assert f"RANK {r} OK" in f.read()


def test_launch_propagates_failure(tmp_path):
    script = tmp_path / "bad.py"
    script.write_text("import sys; sys.exit(3)")
    rc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(tmp_path / "logs"),
         str(script)],
        cwd=REPO, capture_output=True, timeout=120, env=_cpu_env())
    assert rc.returncode == 3


def test_several_local_ranks_are_refused_off_the_cpu(tmp_path, monkeypatch):
    """A TPU chip belongs to one process: without JAX_PLATFORMS=cpu the
    launcher must refuse --nproc_per_node > 1 BEFORE starting a rank
    (it cannot probe for a chip without taking it), and say why."""
    script = tmp_path / "never_runs.py"
    script.write_text("raise SystemExit('a rank was started')")
    env = _cpu_env()
    del env["JAX_PLATFORMS"]
    log_dir = tmp_path / "logs"
    rc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(log_dir), str(script)],
        cwd=REPO, capture_output=True, timeout=120, env=env)
    assert rc.returncode != 0
    assert b"chip belongs to one process" in rc.stderr
    assert not log_dir.exists()

    # in-process: once this process has resolved its backend to the CPU
    # its workers cannot meet a chip, whatever the environment says
    import jax

    from paddle_tpu.framework.device import refuse_chip_sharing

    assert jax.devices()[0].platform == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    refuse_chip_sharing(4, "test")


def test_elastic_detects_dead_node():
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=2)
    m0 = ElasticManager(master, node_id="n0", np=2,
                        heartbeat_interval=0.2, timeout=1.0)
    client = TCPStore("127.0.0.1", master.port, is_master=False,
                      world_size=2)
    m1 = ElasticManager(client, node_id="n1", np=2,
                        heartbeat_interval=0.2, timeout=1.0)
    m0.start()
    m1.start()
    time.sleep(0.5)
    assert m0.dead_nodes(["n0", "n1"]) == []
    m1.stop()  # node 1 dies
    status, dead = m0.watch(["n0", "n1"], poll=0.3)
    assert status == ElasticStatus.RESTART
    assert dead == ["n1"]
    m0.stop()
