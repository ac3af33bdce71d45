"""E2E hang recovery: a rank hangs inside a collective, the runtime
health layer detects it within the deadline, converts the gang to
exit-101, the elastic launcher relaunches, and the resumed run replays
the identical loss trajectory.

Reference analog: fleet/elastic/manager.py's relaunch workflow, extended
to the failure mode it cannot see from the launcher alone — a worker
that is alive (process up, heartbeats flowing) but stuck forever inside
an all-reduce. tests/test_elastic_resume.py proves crash recovery; this
file proves *hang* recovery: chaos injects an infinite sleep at the
``collective.all_reduce`` chaos point on one rank, the hung rank
self-detects its overdue beacon from the monitor thread, peers detect
the aged beacon cross-rank, everyone performs a final step-boundary save
and exits RELAUNCH_EXIT_CODE.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TRAIN = """
import os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

rank = int(os.environ["PADDLE_TRAINER_ID"])
nprocs = int(os.environ["PADDLE_TRAINERS_NUM"])
restart = int(os.environ["PADDLE_RESTART_COUNT"])
ckpt = os.environ["PTQ_CKPT_PATH"] + f".{rank}"
trace = os.environ["PTQ_TRACE_PATH"] + f".{rank}"
final_marker = os.environ["PTQ_FINAL_PATH"] + f".{rank}"

from paddle_tpu.distributed.store import TCPStore
host, port = os.environ["PADDLE_MASTER"].rsplit(":", 1)
store = TCPStore(host, int(port), is_master=False, world_size=nprocs)
# the monitor gets its OWN connection: it must keep beating/checking
# while the main thread may be hung mid-request on its socket
mon_store = TCPStore(host, int(port), is_master=False, world_size=nprocs)

import paddle_tpu as paddle
from paddle_tpu.distributed import all_reduce
from paddle_tpu.runtime import health

snap = {}

def final_save():
    # runs on the MONITOR thread while the main thread may be hung:
    # only touches the step-boundary snapshot handed over below
    if "w" in snap:
        tmp = final_marker + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, w=snap["w"], step=snap["step"])
        os.replace(tmp, final_marker)

mon = health.HealthMonitor(
    mon_store, rank, nprocs, job_id="hang-e2e", restart=restart,
    heartbeat_interval=0.2, heartbeat_timeout=60.0,
    collective_deadline=2.0, final_save=final_save, dump=False)
health.install(mon)
mon.start()

# deterministic full-batch regression, identical on every rank (the
# eager 1-axis all_reduce is an identity — what matters is that it runs
# through _apply_collective's beacon + chaos point every step)
rng = np.random.default_rng(0)
D, STEPS, LR = 8, 6, np.float32(0.1)
X = rng.standard_normal((16, D)).astype(np.float32)
Y = (X @ rng.standard_normal((D, 1)).astype(np.float32))

w = np.zeros((D, 1), np.float32)
start = 0
if os.path.exists(ckpt):
    ck = np.load(ckpt)
    w, start = ck["w"], int(ck["step"])
    print(f"rank {rank} resumed from step {start}", flush=True)

for s_i in range(start, STEPS):
    health.set_step(s_i)
    pred = X @ w
    loss = float(np.mean((pred - Y) ** 2))
    g = 2.0 * X.T @ (pred - Y) / np.float32(X.shape[0])
    w = w - LR * g
    snap["w"], snap["step"] = w.copy(), s_i + 1
    # per-step checkpoint BEFORE the sync point: the hang at step 3
    # resumes from exactly here
    tmp = ckpt + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, w=w, step=s_i + 1)
    os.replace(tmp, ckpt)
    with open(trace, "a") as f:
        f.write(f"{s_i} {loss:.17g}\\n")
    # gradient-sync stand-in: chaos hangs rank 1 here at step 3 of the
    # first generation (rule carries rank=/restart= filters, so the
    # inherited env cannot re-fire after the relaunch)
    all_reduce(paddle.to_tensor(np.float32(loss)))
    store.barrier(f"b{s_i}")

print(f"DONE rank={rank} restart={restart}", flush=True)
sys.exit(0)
"""


def _reference_trajectory():
    """The worker's training loop, replayed in-process: resume must be
    bit-identical, so the comparison is on %.17g strings."""
    rng = np.random.default_rng(0)
    D, steps, lr = 8, 6, np.float32(0.1)
    X = rng.standard_normal((16, D)).astype(np.float32)
    Y = X @ rng.standard_normal((D, 1)).astype(np.float32)
    w = np.zeros((D, 1), np.float32)
    out = []
    for s_i in range(steps):
        pred = X @ w
        out.append(f"{s_i} {float(np.mean((pred - Y) ** 2)):.17g}")
        w = w - lr * (2.0 * X.T @ (pred - Y) / np.float32(X.shape[0]))
    return out


def test_collective_hang_detect_exit101_resume_identical(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent(_TRAIN))
    log_dir = tmp_path / "log"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PTQ_CKPT_PATH"] = str(tmp_path / "ckpt.npz")
    env["PTQ_TRACE_PATH"] = str(tmp_path / "trace")
    env["PTQ_FINAL_PATH"] = str(tmp_path / "final.npz")
    # infinite hang on rank 1, step 3, first generation only
    env["PTQ_CHAOS"] = "hang@collective.all_reduce:step=3,rank=1,restart=0"
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(log_dir),
         "--max_restarts", "2", str(script)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])

    # the health layer converted the hang to exit-101 (the launcher saw
    # it and relaunched the gang — not a crash code, not a kill)
    assert "rc=101" in proc.stderr, proc.stderr[-1500:]
    assert "gang restart 1/" in proc.stderr, proc.stderr[-1500:]

    # a final sync save landed before exit (monitor-thread snapshot save)
    finals = [r for r in range(2)
              if os.path.exists(f"{env['PTQ_FINAL_PATH']}.{r}")]
    assert finals, "no rank performed its final save before exit-101"
    for r in finals:
        ck = np.load(f"{env['PTQ_FINAL_PATH']}.{r}")
        assert int(ck["step"]) == 4  # step-3 boundary snapshot

    logs = [(log_dir / f"workerlog.{r}").read_text() for r in range(2)]
    # the relaunched generation resumed from the step-3 checkpoint and
    # both ranks ran to completion
    assert any("resumed from step 4" in lg for lg in logs), logs
    for r in range(2):
        assert f"DONE rank={r} restart=1" in logs[r], logs[r][-800:]

    # loss trajectory across hang + relaunch is bit-identical to an
    # uninterrupted run: each step appears exactly once, values equal
    # to the 17-significant-digit reprs of the reference replay
    ref = _reference_trajectory()
    for r in range(2):
        lines = (tmp_path / f"trace.{r}").read_text().splitlines()
        assert lines == ref, (r, lines, ref)
