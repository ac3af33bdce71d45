"""A scope's share of its roofline in the traced slice: the reduction that the
readers of ``moe_experts_roofline_pct`` and ``mla_attn_roofline_pct`` share
(``layers/ssm_scan_roofline_pct.py``'s, with the scope, the counters and the
cost function as arguments)."""
import json

from benchmark import flops, kernel_costs, spans


def roofline(run, scope, needs, cost_of, label):
    """``100 x`` least time over the time under ``scope`` in the steps whose
    span holds every counter of ``needs``, or None."""
    sl = spans.traced(run)
    has = lambda args: all(k in args for k in needs)        # noqa: E731
    steps = [s for s in (sl.step_args() if sl else []) if has(s)]
    if not steps:
        return None
    # the steps' stretches on the device's clock, which leads the host's
    whole = [(e.start - sl.lead_ns, e.end - sl.lead_ns)
             for e in sl.whole(spans.ENGINE_SPAN) if has(e.stats)]
    scope_ns = sl.self_ns_where(
        lambda e: scope in spans.scope_of(e).split("/")
        and any(a <= e.start < b for a, b in whole))
    if not scope_ns:
        return None
    cost = {k: sum(cost_of(s)[k] for s in steps) for k in ("bytes", "flops")}
    least = kernel_costs.least_time_s(cost, flops.peaks(run["device_kind"]))
    print(f"bench: {label}: " + json.dumps(dict(
        least, steps=len(steps), scope_s=scope_ns / 1e9, **cost)), flush=True)
    return 100.0 * least["seconds"] / (scope_ns / 1e9)
