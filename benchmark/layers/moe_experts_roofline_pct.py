"""The routed experts' share of their roofline in the traced slice, in %: the
least time the chip could take for the grouped products of the engine steps
wholly inside the slice (``kernel_costs_deepseek_v2.moe_step`` on the
``experts_hit`` and ``moe_pairs`` of each step's ``serve/engine_step`` span;
the larger of bytes over the HBM peak and operations over the bf16 peak of
``peaks.json``) over the self time of those steps' device operations under the
scope ``moe_experts``, whatever implements it. The count is of hit experts
and real rows, so the share cannot pass 100. On a program whose spans lack the
counters the reader finds nothing."""
from benchmark import kernel_costs_deepseek_v2, scope_roofline


def read(run):
    return scope_roofline.roofline(
        run, "moe_experts", ("experts_hit", "moe_pairs"),
        lambda s: kernel_costs_deepseek_v2.moe_step(run["config"], s),
        "moe_experts_roofline")
