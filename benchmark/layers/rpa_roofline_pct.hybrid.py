"""The RPA kernel's share of its roofline in the traced slice of a
Phi-4-mini-flash cell, in %: the least time the chip could take for the
attention of the engine steps wholly inside the slice
(``kernel_costs_phi4flash.rpa_step`` on the arguments of each step's
``serve/engine_step`` span, the model's ``window_kv_tokens`` and
``window_qk_pairs`` among them; the larger of bytes over the HBM peak and
operations over the bf16 peak of ``peaks.json``) over the self time of those
steps' device operations under ``pallas/_rpa_kernel``. The count is of the
algorithm's bytes and operations, not of the zero-padded query rows the
kernel is given, so the share cannot pass 100. On a program whose spans
lack the model's counters the reader finds nothing."""
import json

from benchmark import flops, kernel_costs, kernel_costs_phi4flash, spans


def read(run):
    sl = spans.traced(run)
    steps = [s for s in (sl.step_args() if sl else [])
             if "window_kv_tokens" in s]
    if not steps:
        return None
    # the steps' stretches on the device's clock, which leads the host's
    whole = [(e.start - sl.lead_ns, e.end - sl.lead_ns)
             for e in sl.whole(spans.ENGINE_SPAN)
             if "window_kv_tokens" in e.stats]
    kernel_ns = sl.self_ns_where(
        lambda e: spans.kernel_of(e) in kernel_costs.RPA_KERNELS
        and any(a <= e.start < b for a, b in whole))
    if not kernel_ns:
        return None
    cost = {k: sum(kernel_costs_phi4flash.rpa_step(
        run["config"], run["traffic"], s)[k] for s in steps)
        for k in ("bytes", "flops")}
    least = kernel_costs.least_time_s(cost, flops.peaks(run["device_kind"]))
    print("bench: rpa_roofline.hybrid: " + json.dumps(dict(
        least, steps=len(steps), kernel_s=kernel_ns / 1e9, **cost)),
        flush=True)
    return 100.0 * least["seconds"] / (kernel_ns / 1e9)
