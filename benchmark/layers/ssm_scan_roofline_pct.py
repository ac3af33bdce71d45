"""The selective scan's share of its roofline in the traced slice, in %: the
least time the chip could take for the state updates of the engine steps
wholly inside the slice (``kernel_costs_ssm.scan_step`` on the ``state_rows``
and ``fed_tokens`` of each step's ``serve/engine_step`` span; the larger of
bytes over the HBM peak and operations over the bf16 peak of ``peaks.json``)
over the self time of those steps' device operations under the scope
``ssm_scan``. The count is of the least bytes, so the share cannot pass 100.
The log line says which bound holds."""
import json

from benchmark import flops, kernel_costs, kernel_costs_ssm, spans


def read(run):
    sl = spans.traced(run)
    steps = [s for s in (sl.step_args() if sl else []) if "state_rows" in s]
    if not steps:
        return None
    # the steps' stretches on the device's clock, which leads the host's
    whole = [(e.start - sl.lead_ns, e.end - sl.lead_ns)
             for e in sl.whole(spans.ENGINE_SPAN) if "state_rows" in e.stats]
    scan_ns = sl.self_ns_where(
        lambda e: "ssm_scan" in spans.scope_of(e).split("/")
        and any(a <= e.start < b for a, b in whole))
    if not scan_ns:
        return None
    cost = {k: sum(kernel_costs_ssm.scan_step(run["config"], s)[k]
                   for s in steps) for k in ("bytes", "flops")}
    least = kernel_costs.least_time_s(cost, flops.peaks(run["device_kind"]))
    print("bench: ssm_scan_roofline: " + json.dumps(dict(
        least, steps=len(steps), scan_s=scan_ns / 1e9, **cost)), flush=True)
    return 100.0 * least["seconds"] / (scan_ns / 1e9)
