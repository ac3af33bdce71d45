"""Op-microbenchmark regression harness (tools/ci_op_benchmark.sh
analog): measure -> record baseline -> gate."""
import json
import os
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_measure_record_check_cycle(tmp_path, monkeypatch):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import op_bench

    monkeypatch.setattr(op_bench, "BASELINE",
                        str(tmp_path / "baseline.json"))
    ops = "layernorm_residual,embedding_gather"
    metrics_out = str(tmp_path / "op_metrics.json")
    assert op_bench.main(["--quick", "--record", "--ops", ops,
                          "--metrics-out", metrics_out]) == 0
    with open(op_bench.BASELINE) as f:
        book = json.load(f)
    (key,) = book.keys()
    assert key.endswith("|quick")
    assert set(book[key]) == {"layernorm_residual", "embedding_gather",
                              "__host__"}
    assert all(v > 0 for k, v in book[key].items() if k != "__host__")

    # telemetry sidecar: per-op compile attribution alongside timings
    with open(metrics_out) as f:
        sidecar = json.load(f)
    assert set(sidecar["ops"]) == {"layernorm_residual",
                                   "embedding_gather"}
    for info in sidecar["ops"].values():
        assert info["ms"] > 0
        assert info["compiles"] >= 1  # fresh functions must compile
        assert info["compile_s"] >= 0

    # same machine, immediately after: must pass the gate (generous
    # threshold — tiny-shape CPU timings are noisy; the gate logic is
    # what's under test, not this host's scheduler)
    monkeypatch.setattr(op_bench, "THRESHOLD", 10.0)
    assert op_bench.main(["--quick", "--check", "--ops", ops]) == 0

    # a fabricated 100x-faster baseline must trip the gate
    book[key] = {k: (v if k == "__host__" else v / 100.0)
                 for k, v in book[key].items()}
    with open(op_bench.BASELINE, "w") as f:
        json.dump(book, f)
    assert op_bench.main(["--quick", "--check", "--ops", ops]) == 1

    # --strict: a measured op with no recorded baseline fails the gate
    # instead of slipping through as "skipped"
    monkeypatch.setattr(op_bench, "THRESHOLD", 10.0)
    assert op_bench.main(
        ["--quick", "--check", "--ops", "softmax_ce"]) == 0  # lax: skip
    assert op_bench.main(
        ["--quick", "--check", "--strict", "--ops", "softmax_ce"]) == 1


def test_llama_train_step_rung(tmp_path, monkeypatch):
    """The end-to-end llama-step rung: measurable, recordable, gateable.

    The tools/ci_model_benchmark.sh analog: this CPU rung catches a
    train step that got grossly slower on the same machine. The committed
    tools/op_bench_baseline.json carries the recorded number; here the
    cycle runs against a fresh same-machine baseline so the test cannot
    flake on cross-host speed differences.
    """
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import op_bench

    monkeypatch.setattr(op_bench, "BASELINE",
                        str(tmp_path / "baseline.json"))
    assert op_bench.main(
        ["--quick", "--record", "--ops", "llama_train_step"]) == 0
    with open(op_bench.BASELINE) as f:
        book = json.load(f)
    (key,) = book.keys()
    ms = book[key]["llama_train_step"]
    assert ms > 0
    # gate passes immediately after on the same machine
    monkeypatch.setattr(op_bench, "THRESHOLD", 10.0)
    assert op_bench.main(
        ["--quick", "--check", "--strict", "--ops", "llama_train_step"]) == 0
    # a 100x-faster fabricated baseline trips it
    book[key]["llama_train_step"] = ms / 100.0
    with open(op_bench.BASELINE, "w") as f:
        json.dump(book, f)
    assert op_bench.main(
        ["--quick", "--check", "--ops", "llama_train_step"]) == 1
