"""Self time of the device operations under the Pallas kernels of the fused
MLP block in the traced slice: ``_mlp_fused_kernel`` and
``_mlp_bwd_dx_kernel``. In ms per train step in the slice."""
from benchmark import spans

KERNELS = ("_mlp_fused_kernel", "_mlp_bwd_dx_kernel")


def read(run):
    return spans.kernel_ms_per_step(run, KERNELS, "trace_steps")
