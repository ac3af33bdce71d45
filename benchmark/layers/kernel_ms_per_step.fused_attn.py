"""Self time of the device operations under the Pallas kernels of the fused
attention block in the traced slice: ``_qkv_fused_kernel`` and
``_attn_epi_kernel`` (forward, and again where the backward pass recomputes
them) and the flash backward kernels the block calls. In ms per train step
in the slice."""
from benchmark import spans

KERNELS = ("_qkv_fused_kernel", "_attn_epi_kernel",
           "_flash_bwd_dq_kernel_resident", "_flash_bwd_dkv_kernel_resident",
           "_flash_bwd_dq_kernel_streamed", "_flash_bwd_dkv_kernel_streamed")


def read(run):
    return spans.kernel_ms_per_step(run, KERNELS, "trace_steps")
