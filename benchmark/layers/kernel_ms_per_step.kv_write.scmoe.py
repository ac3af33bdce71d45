"""``kernel_ms_per_step.kv_write`` for the cell of a shortcut-connected-MoE
model: the self time of the device operations under ``pallas/_kv_write_kernel``
(here the step's new latents written into their pages in place, once an
attention sublayer) in the traced slice, in ms per engine step in the slice.
A name of its own because ``tests/benchmark_harness`` pins the accepted
entry's list of cells and a PR may not edit that file (PERF.md section 7)."""
from benchmark import spans


def read(run):
    return spans.kernel_ms_per_step(run, ("_kv_write_kernel",),
                                    "trace_steps")
