"""benchmark/contract.py: what it lets through and what it refuses for a
result line; and BENCHMARK.json held to the driver's characters and to what
the driver asks of every cell."""
import json
import os
import re

import pytest

import bench_testlib  # noqa: F401 — puts the repo root on sys.path
from benchmark import contract, harness

with open(os.path.join(bench_testlib.REPO, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
TRAIN = "deepseek-coder-1.3b.train-2k"
SERVE = "internlm2-1.8b.serve-chat-closed"
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 13958643712}


def result(workload, traced):
    groups = ("end_to_end", "per_layer") if traced else ("end_to_end",)
    metrics = {n: {"value": 12.5, "unit": m["unit"]} for g in groups
               for n, m in contract.cell_metrics(SPEC, workload, g).items()}
    device = dict(DEVICE, window_s=2.0, busy_s=1.5) if traced else dict(DEVICE)
    out = {"correct": True, "attempted": 40, "failed": 0,
           "metrics": metrics, "device": device}
    if traced:
        out["breakdown"] = {"device_ops": [["fusion.1", 0.5]],
                            "idle_gaps": [["a -> b", 0.01]]}
    return out


@pytest.mark.parametrize("workload", [TRAIN, SERVE])
@pytest.mark.parametrize("traced", [False, True])
def test_a_complete_result_passes(workload, traced):
    contract.check_result(result(workload, traced), SPEC, workload, traced)


def test_a_traced_result_may_leave_out_a_metric_its_reader_did_not_find():
    r = result(SERVE, True)
    del r["metrics"]["queue_wait_p95_ms.closed"]
    contract.check_result(r, SPEC, SERVE, True)


def _drop(path):
    def edit(r):
        d = r
        for k in path[:-1]:
            d = d[k]
        del d[path[-1]]
    return edit


def _set(path, value):
    def edit(r):
        d = r
        for k in path[:-1]:
            d = d[k]
        d[path[-1]] = value
    return edit


REFUSED = {
    "missing key": (False, _drop(["failed"]), "lacks the key 'failed'"),
    "missing device": (False, _drop(["device"]), "lacks the key 'device'"),
    "another key": (False, _set(["losses"], [1.0]), "other keys"),
    "breakdown untraced": (False, _set(["breakdown"], {}), "other keys"),
    "correct not bool": (False, _set(["correct"], 1), "not a boolean"),
    "nothing attempted": (False, _set(["attempted"], 0), "positive count"),
    "failed above attempted": (False, _set(["failed"], 41), "within"),
    "undeclared metric": (False, _set(["metrics", "engine_step_ms"],
                                      {"value": 1.0, "unit": "ms"}),
                          "not one the cell"),
    "other cell's metric": (False, _set(["metrics", "train_tokens_per_s"],
                                        {"value": 1.0, "unit": "tokens/s"}),
                            "not one the cell"),
    "missing unit": (False, _set(["metrics", "setup_s"], {"value": 3.0}),
                     "not {'value', 'unit'}"),
    "wrong unit": (False, _set(["metrics", "setup_s"],
                               {"value": 3.0, "unit": "ms"}), "the unit"),
    "value NaN": (False, _set(["metrics", "setup_s", "value"], float("nan")),
                  "has the value"),
    "value a string": (False, _set(["metrics", "setup_s", "value"], "3"),
                       "has the value"),
    "end-to-end zero": (False, _set(["metrics", "serve_tokens_per_s",
                                     "value"], 0.0), "is 0.0"),
    "end-to-end missing": (False, _drop(["metrics", "serve_gap_p95_ms"]),
                           "are missing"),
    "end-to-end missing traced": (True, _drop(["metrics", "setup_s"]),
                                  "are missing"),
    "no memory": (False, _drop(["device", "memory_peak_bytes"]),
                  "memory_peak_bytes"),
    "memory zero": (False, _set(["device", "memory_peak_bytes"], 0),
                    "memory_peak_bytes"),
    "no kind": (False, _drop(["device", "kind"]), "device.kind"),
    "count zero": (False, _set(["device", "count"], 0), "device.count"),
    "busy untraced": (False, _set(["device", "busy_s"], 1.0), "untraced"),
    "busy zero": (True, _set(["device", "busy_s"], 0.0), "device.busy_s"),
    "busy above window": (True, _set(["device", "busy_s"], 2.5),
                          "above device.window_s"),
    "no window": (True, _drop(["device", "window_s"]), "device.window_s"),
    "no busy": (True, _drop(["device", "busy_s"]), "device.busy_s"),
    "no per-layer metric": (True, lambda r: [r["metrics"].pop(n) for n in
                                             contract.cell_metrics(
                                                 SPEC, SERVE, "per_layer")],
                            "none of the cell's per-layer"),
    "breakdown too long": (True, _set(["breakdown", "device_ops"],
                                      [["op", 0.1]] * 11), "at most 10"),
    "breakdown entry": (True, _set(["breakdown", "idle_gaps"],
                                   [["gap", "long"]]), "[name, seconds]"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_malformed_result_is_refused(case):
    traced, edit, message = REFUSED[case]
    r = result(SERVE, traced)
    edit(r)
    with pytest.raises(contract.ContractError, match=re.escape(message)):
        contract.check_result(r, SPEC, SERVE, traced)


def test_benchmark_json_has_the_contract_s_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "benchmark/run.py"]
    assert len(json.dumps(SPEC)) < 64 * 1024
    assert 1 <= SPEC["run_seconds"] <= 51
    sources = {"device_trace", "program_span", "program_counter",
               "host_clock"}
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}, m
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}, m
        assert m["source"] in sources and m["better"] in ("lower", "higher")
    lines = ([c[k] for c in SPEC["configs"] for k in ("source", "why")]
             + [w["why"] for w in SPEC["workloads"]]
             + [m["layer"] for m in SPEC["per_layer"]])
    assert all(1 <= len(x) <= 200 and "\n" not in x and "\t" not in x
               for x in lines)
    assert {c["name"] for c in SPEC["configs"]} == {
        w["config"] for w in SPEC["workloads"]}         # every config is used


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_reports_what_the_driver_asks_of_it(cell):
    """setup_s and another end-to-end metric, a per-layer metric, and every
    per-layer metric moving an end-to-end metric of the same cell."""
    e2e = contract.cell_metrics(SPEC, cell, "end_to_end")
    layer = contract.cell_metrics(SPEC, cell, "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    assert all(m["moves"] in e2e for m in layer.values())
    assert next(w for w in SPEC["workloads"] if w["name"] == cell)[
        "chips"] == 1


def test_every_name_and_unit_holds_only_the_driver_s_characters():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    names = ([c["name"] for c in SPEC["configs"]]
             + [k for c in SPEC["configs"] for k in c["reduced"]]
             + [x for w in SPEC["workloads"]
                for x in (w["name"], w["config"], w["traffic"])]
             + [m["name"] for g in ("end_to_end", "per_layer")
                for m in SPEC[g]])
    assert all(name.match(n) for n in names), names
    assert all(unit.match(m["unit"]) for g in ("end_to_end", "per_layer")
               for m in SPEC[g])
    for base in SPEC["paths"]:          # files under paths: names and "/"
        for dirpath, dirs, files in os.walk(
                os.path.join(bench_testlib.REPO, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f),
                                      bench_testlib.REPO)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_every_cell_has_its_files_and_every_metric_its_reader():
    for c in SPEC["configs"]:
        with open(os.path.join(bench_testlib.REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    for w in SPEC["workloads"]:
        assert os.path.isfile(os.path.join(
            bench_testlib.REPO, "benchmark", "traffic",
            w["traffic"] + ".json"))
    for m in SPEC["per_layer"]:
        reader = harness.find_reader(bench_testlib.REPO, SPEC, m["name"])
        assert callable(harness.load_module(reader).read)


def test_a_split_metric_finds_its_own_reader_before_the_shared_one(tmp_path):
    spec = {"paths": ["extra"]}
    os.makedirs(tmp_path / "extra" / "layers")
    shared = os.path.join(bench_testlib.REPO, "benchmark", "layers",
                          "pallas_share_pct.py")
    assert harness.find_reader(str(tmp_path), spec,
                               "pallas_share_pct.train") == shared
    own = tmp_path / "extra" / "layers" / "pallas_share_pct.train.py"
    own.write_text("def read(run):\n    return 1.0\n")
    assert harness.find_reader(str(tmp_path), spec,
                               "pallas_share_pct.train") == str(own)
    with pytest.raises(FileNotFoundError, match="layers/no_such.py"):
        harness.find_reader(str(tmp_path), spec, "no_such.metric")
