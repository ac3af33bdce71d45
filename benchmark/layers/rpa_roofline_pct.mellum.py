"""The attention kernel's share of its roofline in the traced slice of a
Mellum 2 cell (``"model": "mellum"``), in %: the least time the chip could
take for the attention of the engine steps wholly inside the slice
(``kernel_costs_mellum.attn_step`` on the arguments of each step's
``serve/engine_step`` span, the model's ``window_kv_tokens`` and
``window_qk_pairs`` among them; the larger of bytes over the HBM peak and
operations over the bf16 peak of ``peaks.json``) over the self time of those
steps' device operations under the scope ``attn_kernel`` (the window and
the full layers' kernel, and nothing of the projections). The count is of
the algorithm's bytes and operations, not of the zero-padded query rows the
kernel is given, so the share cannot pass 100. On a program whose spans lack
the model's counters, or that has no such scope, the reader finds
nothing."""
from benchmark import kernel_costs_mellum, scope_roofline


def read(run):
    return scope_roofline.roofline(
        run, "attn_kernel", ("window_kv_tokens", "window_qk_pairs"),
        lambda s: kernel_costs_mellum.attn_step(run["config"], s),
        "rpa_roofline.mellum")
