"""TRUE multi-process distributed tests — 2 CPU processes over
jax.distributed on 127.0.0.1.

Reference analog: test/collective/'s TestDistBase pattern — a launcher
spawns real processes that rendezvous and run collectives, results
compared cross-rank (SURVEY.md §4; VERDICT r2 missing 6: every
`jax.process_count() > 1` branch in distributed/collective.py and the
launch CLI's multi-host path had never executed). The in-process
8-virtual-device tests cover the shard_map branches; THESE cover the
eager multihost_utils branches and the coordination-service bootstrap.
"""
import json
import os
import socket
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_environ():
    """Single CPU device per process, no 8-device forcing
    (the conftest's XLA_FLAGS would otherwise leak into children)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    return env


class TestTwoProcessCollectives:
    def test_allreduce_allgather_broadcast_barrier(self, tmp_path):
        port = _free_port()
        coord = f"127.0.0.1:{port}"
        env = _child_environ()
        procs, paths = [], []
        for pid in range(2):
            res = str(tmp_path / f"result.{pid}.json")
            paths.append(res)
            procs.append(subprocess.Popen(
                [sys.executable, os.path.join(HERE, "dist2proc_child.py"),
                 coord, str(pid), res],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
        outs = [p.communicate(timeout=180)[0] for p in procs]
        for p, o in zip(procs, outs):
            assert p.returncode == 0, o.decode("utf-8", "replace")[-2000:]
        results = [json.load(open(p)) for p in paths]
        for r in results:
            assert r["process_count"] == 2
            assert r["sum"] == [3.0, 30.0]
            assert r["avg"] == 0.5
            assert r["gather"] == [[0.0, -1.0], [1.0, -1.0]]
            assert r["bcast"] == 3.0
            assert r["barrier"] is True


class TestLaunchCliTwoProcess:
    def test_launch_end_to_end(self, tmp_path):
        """One `paddle_tpu.distributed.launch` controller per 'host'
        (rank 0/1), same master — the child trainers bootstrap from the
        env the CLI sets, heartbeat, and all_reduce across processes."""
        port = _free_port()
        master = f"127.0.0.1:{port}"
        res = str(tmp_path / "train_out")
        env = _child_environ()
        procs = []
        for rank in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "paddle_tpu.distributed.launch",
                 "--nnodes", "2", "--master", master, "--rank", str(rank),
                 "--log_dir", str(tmp_path / f"log{rank}"),
                 "--heartbeat_timeout", "120",
                 os.path.join(HERE, "dist2proc_train_child.py"), res],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
        outs = [p.communicate(timeout=180)[0] for p in procs]
        for p, o in zip(procs, outs):
            assert p.returncode == 0, o.decode("utf-8", "replace")[-2000:]
        for rank in range(2):
            r = json.load(open(res + f".{rank}"))
            assert r["world"] == 2 and r["sum"] == 3.0
