"""The held routed experts' share of their roofline in the traced slice of a
shortcut-connected-MoE model (``"model": "longcat_flash"``), in %: the least
time the chip could take for the grouped products of the engine steps wholly
inside the slice (``kernel_costs_longcat_flash.moe_step`` on the
``experts_hit`` and ``held_rows`` that the device counted and the engine put
on each step's ``serve/engine_step`` span; the larger of bytes over the HBM
peak and operations over the bf16 peak of ``peaks.json``) over the self time
of those steps' device operations under the scope ``moe_experts``, whatever
implements it. The count is of hit experts read once and of rows that landed
on a held expert, so the share cannot pass 100. On a program whose spans lack
the counters the reader finds nothing."""
from benchmark import kernel_costs_longcat_flash, scope_roofline


def read(run):
    return scope_roofline.roofline(
        run, "moe_experts", ("experts_hit", "held_rows"),
        lambda s: kernel_costs_longcat_flash.moe_step(run["config"], s),
        "moe_experts_roofline.scmoe")
