"""The benchmark's contract with the driver, as checks.

``check_result`` is called by ``run.py`` on every result object, traced or
not, before it is printed: a malformed line is an error on stderr and a
non-zero exit, never a result. ``BENCHMARK.json`` itself is the driver's to
check; its names and units are held to the driver's characters by a test.
Standard library only.
"""
from __future__ import annotations

import math

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class ContractError(ValueError):
    """A result line that the driver would refuse."""


def _need(ok: bool, what: str) -> None:
    if not ok:
        raise ContractError(what)


def _is_number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def _is_count(x) -> bool:
    return isinstance(x, int) and not isinstance(x, bool) and x >= 0


def _one_line(s, limit: int = 200) -> bool:
    return (isinstance(s, str) and 1 <= len(s) <= limit
            and "\n" not in s and "\t" not in s and "\r" not in s)


def cell_metrics(spec: dict, workload: str, group: str) -> dict:
    """``{name: metric}`` of ``group`` (``end_to_end`` or ``per_layer``) that
    the cell ``workload`` reports: a metric with no ``workloads`` key belongs
    to every cell."""
    return {m["name"]: m for m in spec[group]
            if "workloads" not in m or workload in m["workloads"]}


def check_result(result, spec: dict, workload: str, traced: bool) -> None:
    """Raise ``ContractError`` unless ``result`` is the object the driver
    reads from the last line of a run of ``workload``."""
    _need(isinstance(result, dict), "the result is not a JSON object")
    for key in RESULT_KEYS:
        _need(key in result, f"the result lacks the key {key!r}")
    extra = set(result) - set(RESULT_KEYS) - ({"breakdown"} if traced else set())
    _need(not extra, f"the result has other keys: {sorted(extra)}")
    _need(isinstance(result["correct"], bool), "'correct' is not a boolean")
    _need(_is_count(result["attempted"]) and result["attempted"] > 0,
          f"'attempted' is {result['attempted']!r}, not a positive count")
    _need(_is_count(result["failed"])
          and result["failed"] <= result["attempted"],
          f"'failed' is {result['failed']!r}, not a count within 'attempted'")

    e2e = cell_metrics(spec, workload, "end_to_end")
    layer = cell_metrics(spec, workload, "per_layer")
    metrics = result["metrics"]
    _need(isinstance(metrics, dict) and metrics, "'metrics' is empty")
    # untraced: exactly the cell's end-to-end metrics. traced: its per-layer
    # metrics (a reader that found nothing leaves its metric out, but one at
    # least is there), and the end-to-end ones beside them, all of them.
    allowed = dict(e2e, **layer) if traced else e2e
    for name, m in metrics.items():
        _need(name in allowed, f"metric {name!r} is not one the cell "
              f"{workload} declares for a --trace {int(traced)} run")
        _need(isinstance(m, dict) and set(m) == {"value", "unit"},
              f"metric {name!r} is not {{'value', 'unit'}}: {m!r}")
        _need(_is_number(m["value"]),
              f"metric {name!r} has the value {m['value']!r}")
        _need(m["unit"] == allowed[name]["unit"], f"metric {name!r} has the "
              f"unit {m['unit']!r}, not {allowed[name]['unit']!r}")
    missing = [n for n in e2e if n not in metrics]
    _need(not missing, f"the end-to-end metrics {missing} are missing")
    for name in e2e:
        _need(metrics[name]["value"] > 0,
              f"end-to-end metric {name!r} is {metrics[name]['value']!r}")
    if traced:
        _need(any(n in metrics for n in layer),
              "a traced run reports none of the cell's per-layer metrics")

    dev = result["device"]
    _need(isinstance(dev, dict), "'device' is not an object")
    for key in ("platform", "kind"):
        _need(_one_line(dev.get(key)), f"device.{key} is {dev.get(key)!r}")
    chips = next(w["chips"] for w in spec["workloads"] if w["name"] == workload)
    _need(_is_count(dev.get("count")) and dev["count"] >= chips,
          f"device.count is {dev.get('count')!r}; the cell needs {chips}")
    _need(_is_count(dev.get("memory_peak_bytes"))
          and dev["memory_peak_bytes"] > 0,
          f"device.memory_peak_bytes is {dev.get('memory_peak_bytes')!r}")
    if traced:
        for key in ("window_s", "busy_s"):
            _need(_is_number(dev.get(key)) and dev[key] > 0,
                  f"device.{key} is {dev.get(key)!r} in a traced run")
        _need(dev["busy_s"] <= dev["window_s"], f"device.busy_s "
              f"{dev['busy_s']} is above device.window_s {dev['window_s']}")
        if "breakdown" in result:
            _check_breakdown(result["breakdown"])
    else:
        stray = {"window_s", "busy_s"} & set(dev)
        _need(not stray, f"device has {sorted(stray)} in an untraced run")


def _check_breakdown(b) -> None:
    _need(isinstance(b, dict) and set(b) == {"device_ops", "idle_gaps"},
          "'breakdown' is not {'device_ops', 'idle_gaps'}")
    for key, rows in b.items():
        _need(isinstance(rows, list) and len(rows) <= 10,
              f"breakdown.{key} is not a list of at most 10 entries")
        for row in rows:
            _need(isinstance(row, list) and len(row) == 2
                  and isinstance(row[0], str) and _is_number(row[1])
                  and row[1] >= 0,
                  f"breakdown.{key} has the entry {row!r}, not "
                  "[name, seconds]")
