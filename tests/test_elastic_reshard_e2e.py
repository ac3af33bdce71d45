"""Elastic resize E2E: a chaos ``crash@train.step:...,resize=M`` kill
relaunches the gang at a DIFFERENT world size, and training resumes
sample-exact from the committed checkpoint + manifest cursor.

Proves the PR's acceptance loop end to end: checkpoint written at world
size N restores at world size M (both directions), the global-order
sampler hands out every sample exactly once across the resize, and the
post-resize trajectory matches an uninterrupted single-process run over
the same global batch sequence.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N, D, GBS, STEPS, SEED, LR = 48, 4, 8, 6, 13, 0.05

_TRAIN = f"""
import os, sys
import numpy as np

rank = int(os.environ["PADDLE_TRAINER_ID"])
nprocs = int(os.environ["PADDLE_TRAINERS_NUM"])
restart = int(os.environ["PADDLE_RESTART_COUNT"])
root = os.environ["PTQ_CKPT_ROOT"]
N, D, GBS, STEPS, SEED, LR = {N}, {D}, {GBS}, {STEPS}, {SEED}, {LR}

import jax
jax.config.update("jax_platforms", "cpu")

from paddle_tpu.distributed.store import TCPStore
host, port = os.environ["PADDLE_MASTER"].rsplit(":", 1)
store = TCPStore(host, int(port), is_master=False, world_size=nprocs)

from paddle_tpu.io.sampler import DistributedBatchSampler
from paddle_tpu.io.dataloader import DataLoader
from paddle_tpu.distributed.fault_tolerance import CheckpointManager
from paddle_tpu.testing.chaos import chaos_point

drng = np.random.default_rng(1)
X = drng.standard_normal((N, D)).astype(np.float32)
Y = (X @ drng.standard_normal((D,)).astype(np.float32)).astype(np.float32)

class DS:
    def __len__(self):
        return N
    def __getitem__(self, i):
        return X[i], Y[i], np.int64(i)

# the GLOBAL batch size is world-size invariant: per-rank share shrinks
# or grows with the gang, the trajectory does not
bs = GBS // nprocs
smp = DistributedBatchSampler(DS(), bs, num_replicas=nprocs, rank=rank,
                              shuffle=True, seed=SEED)
loader = DataLoader(DS(), batch_sampler=smp)
mgr = CheckpointManager(root, backend="pickle", keep=3).attach_data(loader)
state, start = mgr.restore()
w = np.asarray(state["w"]) if state is not None else np.zeros(D, np.float32)
if start:
    print(f"rank {{rank}} resumed from step {{start}} at world {{nprocs}}",
          flush=True)

def tonp(t):
    return np.asarray(t.numpy() if hasattr(t, "numpy") else t)

def allreduce(vec, tag):
    buf = np.asarray(vec, np.float64)
    store.set(f"ar/{{restart}}/{{tag}}/{{rank}}", buf.tobytes())
    tot = np.zeros_like(buf)
    for r in range(nprocs):
        raw = store.wait(f"ar/{{restart}}/{{tag}}/{{r}}")
        tot = tot + np.frombuffer(raw, np.float64).reshape(buf.shape)
    return tot

step, loss, it = start, None, iter(loader)
while step < STEPS:
    try:
        batch = next(it)
    except StopIteration:
        it = iter(loader)
        continue
    xs, ys = tonp(batch[0]), tonp(batch[1])
    ids = tonp(batch[2]).astype(int)
    step += 1
    err = xs @ w - ys
    gsum = 2.0 * xs.T @ err            # sum over the local slice
    tot = allreduce(np.concatenate([gsum, [float(np.sum(err ** 2))]]),
                    f"s{{step}}")
    grad, loss = tot[:D] / GBS, float(tot[D] / GBS)
    w = (w - LR * grad).astype(np.float32)
    print(f"SAMPLES gen={{restart}} step={{step}} rank={{rank}} "
          f"world={{nprocs}} ids={{','.join(map(str, ids.tolist()))}}",
          flush=True)
    if rank == 0:
        mgr.save(step, {{"w": w, "step": step}})
    store.barrier(f"b{{restart}}s{{step}}")  # commit visible gang-wide
    chaos_point("train.step", step=step)

# uninterrupted single-process reference over the SAME global order
order = np.random.RandomState(SEED).permutation(N).tolist()
w_ref = np.zeros(D, np.float32)
for k in range(STEPS):
    idx = order[k * GBS:(k + 1) * GBS]
    err = X[idx] @ w_ref - Y[idx]
    w_ref = (w_ref - LR * (2.0 * X[idx].T.astype(np.float64) @ err
                           / GBS)).astype(np.float32)
np.testing.assert_allclose(w, w_ref, rtol=1e-4, atol=1e-5)
print(f"RESULT gen={{restart}} rank={{rank}} loss={{loss:.8f}} "
      f"w={{','.join(f'{{v:.6f}}' for v in w.tolist())}}", flush=True)
sys.stdout.flush()
os._exit(0)
"""


def _run_elastic(tmp_path, nproc, max_nproc, chaos_spec):
    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent(_TRAIN))
    log_dir = tmp_path / "log"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PTQ_CKPT_ROOT"] = str(tmp_path / "ckpt")
    env["PTQ_CHAOS"] = chaos_spec
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--elastic", "--nproc_per_node", str(nproc),
         "--min_nproc", "1", "--max_nproc", str(max_nproc),
         "--log_dir", str(log_dir), "--max_restarts", "0", str(script)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420)
    return proc, log_dir


def _samples(log_dir):
    recs = []
    for f in sorted(log_dir.glob("workerlog.*")):
        for ln in f.read_text().splitlines():
            if ln.startswith("SAMPLES "):
                d = dict(kv.split("=", 1) for kv in ln.split()[1:])
                recs.append({"gen": int(d["gen"]), "step": int(d["step"]),
                             "rank": int(d["rank"]),
                             "world": int(d["world"]),
                             "ids": [int(x) for x in d["ids"].split(",")]})
    return recs


def _check_resize_run(proc, log_dir, crash_step, world0, world1):
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    # the kill got a free relaunch (max_restarts=0 proves it burned no
    # budget), whichever supervisor check saw the scale event first
    assert ("worker requested relaunch (exit 101)" in proc.stderr
            or "scale event" in proc.stderr), proc.stderr[-1500:]

    logs = "".join((log_dir / f"workerlog.{r}").read_text()
                   for r in range(max(world0, world1))
                   if (log_dir / f"workerlog.{r}").exists())
    assert f"resumed from step {crash_step} at world {world1}" in logs

    order = np.random.RandomState(SEED).permutation(N).tolist()
    recs = _samples(log_dir)
    for step in range(1, STEPS + 1):
        gen, world = (0, world0) if step <= crash_step else (1, world1)
        at = sorted((r for r in recs if r["step"] == step),
                    key=lambda r: r["rank"])
        assert [(r["gen"], r["world"]) for r in at] == \
            [(gen, world)] * world, (step, at)
        got = [i for r in at for i in r["ids"]]
        # rank-order concatenation IS the global order chunk: every
        # sample consumed exactly once across the resize
        assert got == order[(step - 1) * GBS:step * GBS], step

    finals = [ln for f in log_dir.glob("workerlog.*")
              for ln in f.read_text().splitlines()
              if ln.startswith("RESULT gen=1")]
    assert len(finals) == world1, finals
    assert len({ln.split("w=")[1] for ln in finals}) == 1, finals


def test_kill_with_resize_4_to_2(tmp_path):
    """Gen 0 trains at world 4; a chaos kill at step 3 publishes a scale
    request for 2 and the relaunched gang finishes at world 2."""
    proc, log_dir = _run_elastic(
        tmp_path, nproc=4, max_nproc=4,
        chaos_spec="crash@train.step:step=3,rank=0,restart=0,"
                   "resize=2,exit_code=101")
    _check_resize_run(proc, log_dir, crash_step=3, world0=4, world1=2)


def test_kill_with_resize_2_to_4(tmp_path):
    """The growth direction: preempted at world 2, relaunched at 4."""
    proc, log_dir = _run_elastic(
        tmp_path, nproc=2, max_nproc=4,
        chaos_spec="crash@train.step:step=3,rank=0,restart=0,"
                   "resize=4,exit_code=101")
    _check_resize_run(proc, log_dir, crash_step=3, world0=2, world1=4)
