"""What every cell shares: finding a cell's files by the names in
``BENCHMARK.json``, running its kind, reading its per-layer metrics and
assembling the result object. No file here knows a configuration, a traffic
mix, a kind of cell or a per-layer metric by name.

Files are looked up under each directory of ``paths`` and then in this
directory, so a later PR adds a cell with new files and one ``workloads``
entry:

  configs/<config>.json   the sizes as they are run (named by ``configs[].file``)
  traffic/<traffic>.json  parameters of a traffic mix or training job; its
                          ``kind`` names the module that runs it
  kinds/<kind>.py         ``run(ctx) -> dict`` for one kind of cell
  layers/<metric>.py      ``read(run) -> number or None`` for one per-layer
                          metric; a metric split by cells (``x.train``,
                          ``x.serve``) may share the reader ``layers/x.py``
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import threading
import time

from benchmark import contract, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def log(msg: str) -> None:
    """An earlier line of stdout: the result is only ever the last one."""
    print(f"bench: {msg}", flush=True)


def load_spec(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(spec: dict, workload: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; it has "
                   f"{[w['name'] for w in spec['workloads']]}")


def find_file(root: str, spec: dict, rel: str) -> str:
    """``rel`` under the first directory of ``paths`` (then this one) that
    has it."""
    tried = [os.path.join(root, p) for p in spec["paths"]] + [HERE]
    for base in tried:
        path = os.path.join(base, rel)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"{rel} is in none of {tried}")


def find_reader(root: str, spec: dict, metric: str) -> str:
    """The reader of the per-layer metric ``metric``: ``layers/<metric>.py``,
    or for a metric split by cells (``x.train``) ``layers/x.py``."""
    try:
        return find_file(root, spec, f"layers/{metric}.py")
    except FileNotFoundError:
        if "." not in metric:
            raise
        return find_file(root, spec, f"layers/{metric.split('.')[0]}.py")


def load_module(path: str):
    """The module in the file ``path`` (metric names hold dots, so these
    files are loaded by path, not imported by name)."""
    name = "benchmark_file_" + re.sub(r"\W", "_", os.path.abspath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Context:
    """What a kind's ``run`` is given."""
    config: dict          # the configuration file, parsed
    traffic: dict         # the traffic or job file, parsed
    seed: int
    seconds: float
    trace: bool
    t_start: float        # perf_counter() at process start, for setup_s
    profile_dir: str      # where a traced run writes its profile

    def since_start(self) -> str:
        """Seconds since the process started, for the set-up's log lines."""
        return f"{time.perf_counter() - self.t_start:.1f} s"

    def key(self):
        """A JAX PRNG key from ``--seed``, which may pass 32 bits."""
        import jax
        return jax.random.PRNGKey(self.seed % (2**31 - 1))


def build_config(config: dict):
    """The program's ``LlamaConfig`` from a configuration file: every key
    of the file that is a field of ``LlamaConfig``, ``dtype`` by name."""
    import jax.numpy as jnp
    from paddle_tpu.models import llama
    names = {f.name for f in dataclasses.fields(llama.LlamaConfig)}
    kw = {k: v for k, v in config.items() if k in names}
    kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16")).type
    return llama.LlamaConfig(**kw)


def pallas_kernels(lowered_text: str) -> list:
    """Kernel names of the Mosaic custom calls in a lowered program."""
    return sorted(set(re.findall(r'kernel_name = "([^"]+)"', lowered_text)))


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, from ``memory_stats()``."""
    import jax
    stats = [d.memory_stats() for d in jax.local_devices()]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        raise RuntimeError("this backend reports no peak_bytes_in_use")
    log(f"memory_stats of device 0: {json.dumps(stats[0])}")
    return int(max(s["peak_bytes_in_use"] for s in stats))


def host_steal_s():
    """Seconds, summed over the CPUs, that this machine's hypervisor gave
    to others so far (``/proc/stat``), or None where that is not reported:
    the difference over a window says whether a stalled step was the
    hypervisor's doing. (The chip's machine shows no control group's
    ``cpu.stat`` and no ``/proc/pressure`` to read beside it.)"""
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        return int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return None


def slowest(turns: list, n: int = 3) -> list:
    """The ``n`` longest loop turns of a window, for the log: a stall shows
    here and in no percentile. ``turns`` holds one dict of seconds per turn,
    ``wall`` and the parts the kind could tell apart; they come back in ms
    with their index, so that a stall can be laid at one part's door."""
    worst = sorted(range(len(turns)), key=lambda i: -turns[i]["wall"])[:n]
    return [dict({k: round(v * 1e3, 2) for k, v in turns[i].items()}, turn=i)
            for i in worst]


def settle_collector() -> None:
    """Collect now and move what set-up left behind (the imports alone are
    over a million objects) out of the collector's sight, so that a full
    collection inside the window walks only the window's own objects."""
    gc.collect()
    gc.freeze()


class Heartbeat:
    """A thread that sleeps 5 ms at a time through a ``with`` block and keeps
    the longest gap between two of its wake-ups. It tells a stalled loop
    turn in which only the main thread waited (the device, or a call that
    released the interpreter: the heartbeat goes on) from one in which the
    whole process stood still (a frozen machine or a held interpreter: the
    heartbeat has the same gap)."""

    def __init__(self):
        self.worst_s, self.worst_at = 0.0, 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True)

    def _beat(self):
        last = time.perf_counter()
        while not self._stop.wait(0.005):
            now = time.perf_counter()
            if now - last > self.worst_s:
                self.worst_s, self.worst_at = now - last, last
            last = now

    def __enter__(self) -> "Heartbeat":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()

    def report(self, t0: float) -> dict:
        """The longest gap in ms and when it began, in seconds after
        ``t0``."""
        return {"longest_gap_ms": round(self.worst_s * 1e3, 2),
                "at_s": round(self.worst_at - t0, 3)}


def device_block() -> dict:
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


class TraceSlice:
    """The profiler over a short slice of the window, started and stopped
    between steps with the device fenced on each side. ``reduce()`` gives
    ``trace_reduce.reduce_dir``'s dict for the slice."""

    def __init__(self, profile_dir: str):
        self.dir = profile_dir
        self.started = self.active = False
        self.host_s = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        # no Python tracer: it would slow the host code being measured
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.started = self.active = True
        self._slice = jax.profiler.TraceAnnotation(trace_reduce.SLICE_NAME)
        self._slice.__enter__()
        self._t0 = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self._t0

    def stop(self) -> None:
        import jax
        self.host_s = self.elapsed()
        self._slice.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def reduce(self) -> dict:
        if not self.started or self.active:
            raise RuntimeError("the window was too short for its traced "
                               "slice: the profiler never ran to its end")
        return trace_reduce.reduce_dir(self.dir, host_window_s=self.host_s)


def run_cell(root: str, spec: dict, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float) -> dict:
    """Run one cell on whatever device JAX has and return the result
    object, not yet checked. ``run.py`` refuses to call this without a TPU;
    the tests call it on the CPU at a tiny size."""
    cell = find_cell(spec, workload)
    cfg_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(find_file(root, spec, f"traffic/{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    kind = load_module(find_file(root, spec, f"kinds/{traffic['kind']}.py"))
    ctx = Context(config=config, traffic=traffic, seed=int(seed), seconds=float(seconds),
                  trace=bool(trace), t_start=t_start,
                  profile_dir=os.path.join(root, ".bench_trace", workload))
    log(f"cell {workload}: config {cell['config']}, traffic "
        f"{cell['traffic']} (kind {traffic['kind']}), seed {ctx.seed}, "
        f"{ctx.seconds:g} s, trace {int(ctx.trace)}")
    run = kind.run(ctx)

    device = dict(device_block(), memory_peak_bytes=run["memory_peak_bytes"])
    e2e = contract.cell_metrics(spec, workload, "end_to_end")
    missing = sorted(set(e2e) - set(run["end_to_end"]))
    if missing:
        raise KeyError(f"kind {traffic['kind']} gives no value for the "
                       f"end-to-end metrics {missing} of cell {workload}")
    metrics = {n: {"value": run["end_to_end"][n], "unit": m["unit"]}
               for n, m in e2e.items()}
    result = {"correct": bool(run["correct"]),
              "attempted": int(run["attempted"]),
              "failed": int(run["failed"]), "metrics": metrics,
              "device": device}
    if trace:
        reduced = run["trace"]
        device.update(window_s=reduced["window_s"], busy_s=reduced["busy_s"])
        run = dict(run, config=config, traffic=traffic,
                   device_kind=device["kind"])
        for name, m in contract.cell_metrics(
                spec, workload, "per_layer").items():
            value = load_module(find_reader(root, spec, name)).read(run)
            if value is None:
                log(f"per-layer metric {name}: its reader found nothing")
                continue
            metrics[name] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    return result


def print_result(result: dict, spec: dict, workload: str, trace: bool) -> int:
    """The one door to the last line: the result is printed only if the
    contract's checks pass; otherwise the reason goes to stderr."""
    try:
        contract.check_result(result, spec, workload, trace)
    except contract.ContractError as exc:
        print(f"bench: the result object breaks the contract, so no result "
              f"is printed: {exc}\nbench: it was {json.dumps(result)}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0
