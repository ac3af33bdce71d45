"""Serving chaos E2E (ISSUE 11 acceptance).

Two scenarios; the first in a fresh interpreter so chaos rules, metrics,
and compiled caches cannot leak into (or out of) the suite:

1. **Replica kill mid-decode** — ``PTQ_CHAOS`` kills replica r0 at its
   per-replica chaos point while half the streams are mid-decode. The
   script first computes the uninterrupted single-engine reference
   in-process (safe: the rule only matches ``serve.replica.r0.step``),
   then serves the same prompts through a 2-replica Router. Every
   stream must fail over and finish **bit-identical** to the reference,
   with each token delivered to the stream callback exactly once.

2. **Overload** — an in-process ``LLMEngine`` driven at far beyond
   queue capacity (requests ≫ ``max_queue``): admission must shed with
   typed retriable rejections (counted, not crashed), every admitted
   request must complete, and the TTFT p95 must sit inside the
   configured SLO in ``slo_report()``.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_KILL = """
import json, os
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np

from paddle_tpu import serving
from paddle_tpu.models import llama
from paddle_tpu.models.decoding import init_kv_cache
from paddle_tpu.ops import pallas_ops

pallas_ops._INTERPRET = True

cfg = llama.LlamaConfig(
    vocab_size=128, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=64, dtype=jnp.float32, use_remat=False)
params = llama.init_params(cfg, jax.random.PRNGKey(0))

rng = np.random.RandomState(7)
prompts = [[int(t) for t in rng.randint(0, 128, rng.randint(4, 12))]
           for _ in range(8)]
N_NEW = 8

def dense_greedy(prompt, n):
    cache = init_kv_cache(cfg.num_hidden_layers, 1, len(prompt) + n,
                          cfg.num_key_value_heads, cfg.head_dim,
                          dtype=jnp.float32)
    ids = jnp.asarray([prompt], jnp.int32)
    logits, cache = llama.forward_with_cache(cfg, params, ids, cache, 0)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(n - 1):
        logits, cache = llama.forward_with_cache(
            cfg, params, jnp.asarray([[out[-1]]], jnp.int32), cache, pos)
        out.append(int(jnp.argmax(logits[0, 0])))
        pos += 1
    return out

# uninterrupted reference: the PTQ_CHAOS rule in the environment only
# matches serve.replica.r0.step, so plain decoding is untouched
ref = [dense_greedy(p, N_NEW) for p in prompts]

def make_engine():
    return serving.LLMEngine(cfg, params, max_running=4, chunk=4,
                             page_size=8, max_model_len=32)

router = serving.Router([("r0", make_engine()), ("r1", make_engine())],
                        heartbeat_timeout=1e6)
streamed = {}
def on_tok(gid, tok, done):
    streamed.setdefault(gid, []).append(tok)

gids = [router.submit(p, N_NEW, on_token=on_tok) for p in prompts]
out = router.run(max_steps=1000)

stats = serving.serving_stats()
print("KILL_E2E " + json.dumps({
    "ref": ref,
    "out": [out[g] for g in gids],
    "streamed": [streamed.get(g, []) for g in gids],
    "states": router.replica_states(),
    "failovers": int(stats["failovers"]),
    "replicas_dead": int(stats["replicas_dead"]),
    "migrations": [router._requests[g].migrations for g in gids],
}), flush=True)
"""


def _run(cmd, env, timeout=420):
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


def _grab_json(stdout, tag):
    lines = [ln for ln in stdout.splitlines() if ln.startswith(tag)]
    assert lines, f"no {tag} line in output"
    return json.loads(lines[-1][len(tag):])


def _base_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_replica_kill_failover_bit_identical(tmp_path):
    script = tmp_path / "kill_e2e.py"
    script.write_text(textwrap.dedent(_KILL))
    env = _base_env()
    # kill replica r0 at its 3rd router step: prefills have landed on
    # both replicas and several streams are mid-decode on the victim
    env["PTQ_CHAOS"] = "kill@serve.replica.r0.step:step=3"
    proc = _run([sys.executable, str(script)], env)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    res = _grab_json(proc.stdout, "KILL_E2E ")

    assert res["states"]["r0"] == "dead"
    assert res["states"]["r1"] == "live"
    assert res["replicas_dead"] == 1
    assert res["failovers"] >= 1 and sum(res["migrations"]) >= 1

    # every stream — including the ones torn off the dead replica —
    # matches the uninterrupted reference token-for-token, and the
    # callback saw each token exactly once (idempotent replay)
    for i, (r, o, s) in enumerate(
            zip(res["ref"], res["out"], res["streamed"])):
        assert o == r, f"stream {i} diverged after failover"
        assert s == r, f"stream {i} re-delivered tokens on failover"


def test_overload_sheds_bounded_and_meets_ttft_slo(monkeypatch):
    import jax

    from paddle_tpu import serving
    from paddle_tpu.models import llama
    from paddle_tpu.ops import pallas_ops
    from paddle_tpu.serving import workloads

    monkeypatch.setattr(pallas_ops, "_INTERPRET", True)
    cfg = llama.preset("llama-debug")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    n_req, n_new, prompt_len, chunk = 32, 8, 12, 8
    # generous targets: CPU-interpret timing only needs to prove the
    # verdict plumbing, not TPU-grade latency
    eng = serving.LLMEngine(
        cfg, params, max_running=4, chunk=chunk, max_queue=8,
        max_model_len=prompt_len + n_new + chunk,
        slo=serving.SLOConfig(ttft_p95_s=60.0, latency_p95_s=120.0))
    arrivals = workloads.generate(
        "uniform", n_req, prompt_len=prompt_len, max_new_tokens=n_new,
        vocab=cfg.vocab_size)
    base = serving.serving_stats()
    rids, shed = [], 0

    def submit(a):
        nonlocal shed
        try:
            rids.append(eng.add_request(list(a.prompt), a.max_new_tokens))
        except serving.AdmissionRejected:
            shed += 1

    # 2x+ overload against an 8-deep queue: half the requests at once,
    # the rest arriving while the batch is in flight
    for a in arrivals[:n_req // 2]:
        submit(a)
    pending = arrivals[n_req // 2:]
    steps = 0
    while eng.has_work() or pending:
        if pending and steps % 2 == 1:
            submit(pending.pop(0))
        eng.step()
        steps += 1
        assert steps < 10000, "serve loop did not converge"
    now = serving.serving_stats()

    # shedding happened, typed and counted, and bounded
    assert shed > 0
    assert int(now["shed"] - base["shed"]) == shed < n_req
    # nothing admitted was lost, no recovery path was exercised
    assert all(len(eng.output_of(r)) == n_new for r in rids)
    assert now["quarantined"] == base["quarantined"]
    assert now["deadline_expired"] == base["deadline_expired"]
    # the SLO verdicts are computed and pass under the generous targets
    rep = eng.slo_report()
    assert rep["ttft_ok"] is True and rep["latency_ok"] is True, rep
    assert rep["ttft_p95_s"] <= 60.0
    assert len(eng._step_fns) == 2
    eng.shutdown()
