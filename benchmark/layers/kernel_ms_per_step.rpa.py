"""Self time of the device operations under ``pallas/_rpa_kernel`` (ragged
paged attention; ``_rpa_kernel_quant`` with int8 pages) in the traced
slice, in ms per engine step in the slice."""
from benchmark import kernel_costs, spans


def read(run):
    return spans.kernel_ms_per_step(run, kernel_costs.RPA_KERNELS,
                                    "trace_steps")
