"""Shared by the benchmark's tests: a benchmark root in a temporary
directory whose cell, configuration, traffic mixes and per-layer metric are
all NEW files — nothing under ``benchmark/`` is touched to add them."""
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

DEBUG_CONFIG = {      # llama-debug widths: a CPU test size, never a cell
    "source": "tests only", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "dtype": "bfloat16", "reduced": []}

TINY_TRAIN = {
    "kind": "train", "batch": 2, "seq": 32, "zipf_a": 1.2,
    "trace_steps": 2,
    "check": {"ce0_abs_tol": 0.01, "grad_dir_tol": 0.05,
              "require_pallas_kernel": False}}

TINY_CLOSED = {
    "kind": "serve-closed", "clients": 4,
    "engine": {"max_running": 4, "max_model_len": 128, "page_size": 16},
    "prompt": {"median": 24, "sigma": 0.6, "min": 4, "max": 64},
    "output": {"median": 8, "sigma": 0.5, "min": 3, "max": 16},
    "n_lengths": 32, "round": 4, "ramp_s": 0.3, "trace_slice_s": 0.2,
    "check": {"sample": 2, "sample_max_tokens": 80, "logits_rel_tol": 0.05,
              "token_gap_sigma_tol": 0.3, "require_pallas_kernel": False}}

# a per-layer metric that no file under benchmark/ knows
STEPS_COUNTED = '''
def read(run):
    return float(run["counters"]["steps"]) if "steps" in run["counters"] \\
        else None
'''


def metric(name, unit, **kw):
    return dict({"name": name, "unit": unit, "better": "higher",
                 "source": "host_clock"}, **kw)


def make_root(tmp_path) -> str:
    """A benchmark root under ``tmp_path`` with two cells made of new files
    only; returns its path."""
    root = str(tmp_path)
    extra = os.path.join(root, "extra")
    for sub in ("configs", "traffic", "layers"):
        os.makedirs(os.path.join(extra, sub))
    files = {"configs/debug.json": json.dumps(DEBUG_CONFIG),
             "traffic/tiny-train.json": json.dumps(TINY_TRAIN),
             "traffic/tiny-closed.json": json.dumps(TINY_CLOSED),
             "layers/steps_counted.py": STEPS_COUNTED}
    for rel, text in files.items():
        with open(os.path.join(extra, rel), "w") as f:
            f.write(text)
    train, serve = "debug.tiny-train", "debug.tiny-closed"
    spec = {
        "command": ["python3", "benchmark/run.py"], "paths": ["extra"],
        "run_seconds": 2,
        "configs": [{"name": "debug", "source": "tests only",
                     "file": "extra/configs/debug.json", "reduced": [],
                     "why": "CPU test size"}],
        "workloads": [
            {"name": train, "config": "debug", "traffic": "tiny-train",
             "chips": 1, "why": "train kind end to end"},
            {"name": serve, "config": "debug", "traffic": "tiny-closed",
             "chips": 1, "why": "serve-closed kind end to end"}],
        "end_to_end": [
            metric("train_tokens_per_s", "tokens/s", bound=0.05,
                   workloads=[train]),
            metric("serve_tokens_per_s", "tokens/s", bound=0.05,
                   workloads=[serve]),
            metric("serve_gap_p95_ms", "ms", bound=0.05, workloads=[serve]),
            metric("setup_s", "s", bound=0.1)],
        "per_layer": [
            metric("train_step_ms", "ms", layer="model step",
                   moves="train_tokens_per_s", workloads=[train]),
            metric("pallas_share_pct.train", "%", layer="kernels",
                   moves="train_tokens_per_s", workloads=[train]),
            metric("engine_step_ms", "ms", layer="engine",
                   moves="serve_gap_p95_ms", workloads=[serve]),
            metric("queue_wait_p95_ms.closed", "ms",
                   layer="scheduler and cache", moves="serve_tokens_per_s",
                   workloads=[serve]),
            metric("ttft_p95_ms.closed", "ms", layer="engine",
                   moves="serve_tokens_per_s", workloads=[serve]),
            metric("host_gap_ms_per_step", "ms", layer="device",
                   moves="serve_gap_p95_ms", workloads=[serve]),
            metric("steps_counted", "steps", layer="engine",
                   moves="serve_tokens_per_s", workloads=[serve])]}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root
