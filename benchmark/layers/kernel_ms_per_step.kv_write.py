"""Self time of the device operations under ``pallas/_kv_write_kernel`` (the
step's new K/V, or latents, written into their pages in place: the successor
of what ``kv_pool_copy_ms_per_step`` timed before PR 26) in the traced slice,
in ms per engine step in the slice."""
from benchmark import spans


def read(run):
    return spans.kernel_ms_per_step(run, ("_kv_write_kernel",),
                                    "trace_steps")
