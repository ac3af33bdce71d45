"""``python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: run one cell of ``BENCHMARK.json`` on the TPU this machine
holds and print its result object as the last line of stdout.

Without a TPU, or with fewer chips than the cell asks for, it names the
device it found and exits non-zero with no result line: it never measures
on a CPU. Every result object passes ``benchmark/contract.py`` before it is
printed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# the persistent compile cache at a fixed path inside the checkout, unless
# the machine names one; the program (core/compile_cache.py) then sets none
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmark import harness
    spec = harness.load_spec(ROOT)
    cell = harness.find_cell(spec, args.workload)

    import jax
    device = harness.device_block()
    harness.log(f"device {json.dumps(device)}")
    if device["platform"] != "tpu" or device["count"] < cell["chips"]:
        print(f"bench: cell {args.workload} needs {cell['chips']} TPU "
              f"chip(s) and JAX found {json.dumps(device)}; nothing is "
              "measured without them", file=sys.stderr)
        return 1
    # every program of set-up goes to the cache, the short compiles too:
    # the program's own threshold (2 s) would recompile them in every run
    from paddle_tpu.core import compile_cache
    compile_cache.ensure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    result = harness.run_cell(ROOT, spec, args.workload, args.seed,
                              args.seconds, bool(args.trace), T_START)
    return harness.print_result(result, spec, args.workload,
                                bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())
