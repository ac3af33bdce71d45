"""Self time of the device operations under the scope ``ssm_proj`` (a Mamba
mixer's per-token work: norm and ``w_in``; ``w_x``, the ``dt`` chain and the
B and C norms; the gate, ``D_skip`` and ``w_out``) in the traced slice, in ms
per engine step in the slice. Also logs ``bench: device_by_mamba_part``: the
time under ``mamba`` by the mixer's four names (``ssm_proj``, ``ssm_conv``,
``ssm_scan``, ``step_layout``) and what is under none of them, the residual
add, which should read near zero."""
from benchmark import step_budget


def read(run):
    return step_budget.scope_ms_per_step(
        run, step_budget.PROJ, "device_by_mamba_part",
        step_budget.mamba_part_of)
