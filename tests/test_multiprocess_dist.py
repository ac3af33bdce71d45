"""Real multi-process distributed correctness.

Reference analog: test_dist_base.py:899 (TestDistBase) /
_run_cluster_nccl2:1558 — spawn actual trainer processes on local free
ports, rendezvous, run collectives, train, and assert loss parity with
single-process execution. Every other distributed test in this suite
runs one process over 8 virtual devices; this one exercises a genuine
process gang: jax.distributed.initialize bootstrapped through the native
TCPStore, cross-process psum/all_gather, and 3 DP training steps.
"""
import json
import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_ports(n):
    # hold every socket open until all ports are read, so the OS cannot
    # hand the same ephemeral port out twice
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _launch_gang(nprocs, timeout=420, worker="dist_worker.py",
                 devices_per_proc=1):
    store_port, coord_port = _free_ports(2)
    procs = []
    for rank in range(nprocs):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # devices_per_proc=1: the gang itself is the parallelism;
        # >1: multi-host GSPMD (n processes x m virtual devices each)
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            + str(devices_per_proc))
        env["PTQ_DEVICES_PER_PROC"] = str(devices_per_proc)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_TRAINERS_NUM"] = str(nprocs)
        env["PTQ_STORE_PORT"] = str(store_port)
        env["PTQ_COORD_PORT"] = str(coord_port)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tests", worker)],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return outs


@pytest.mark.parametrize("nprocs", [2, 4])
def test_gang_collectives_and_dp_parity(nprocs):
    outs = _launch_gang(nprocs)
    results = []
    for rc, out, err in outs:
        assert rc == 0, (rc, out[-1500:], err[-1500:])
        line = next(l for l in out.splitlines() if l.startswith("RESULT:"))
        results.append(json.loads(line[len("RESULT:"):]))

    want_sum = nprocs * (nprocs + 1) / 2.0
    want_gather = [float(i + 1) for i in range(nprocs)]
    ranks = sorted(r["rank"] for r in results)
    assert ranks == list(range(nprocs))
    for r in results:
        assert r["world"] == nprocs
        assert r["allreduce"] == want_sum
        assert r["allgather"] == want_gather
    # every rank saw identical losses (replicated params, global psum) —
    # and the worker itself asserted parity with the single-process run
    for a, b in zip(results, results[1:]):
        assert a["losses"] == b["losses"]


def test_hybrid_mesh_across_process_boundary():
    """Multi-host GSPMD: 2 processes x 4 virtual devices = one global
    8-device mesh, with the pipeline, the ring-attention, and the
    dedicated ZeRO sharding axis each spanning the process boundary.
    Each rank asserts CE parity against its locally computed
    single-device reference (the worker raises on mismatch); here we
    additionally require both ranks to agree."""
    outs = _launch_gang(2, timeout=900, worker="hybrid_dist_worker.py",
                        devices_per_proc=4)
    results = []
    for rc, out, err in outs:
        assert rc == 0, (rc, out[-2000:], err[-2000:])
        line = next(l for l in out.splitlines() if l.startswith("RESULT:"))
        results.append(json.loads(line[len("RESULT:"):]))
    assert sorted(r["rank"] for r in results) == [0, 1]
    for r in results:
        labels = [v["label"] for v in r["variants"]]
        assert labels == ["pp-xproc", "cp-xproc", "zero-xproc"], labels
    for a, b in zip(results, results[1:]):
        for va, vb in zip(a["variants"], b["variants"]):
            assert va["ce"] == vb["ce"], (va, vb)
