"""Self time of the device operations under the scope ``moe`` (an expert
layer's whole feed-forward: router, sort and gather, the routed experts,
the shared expert, un-sort and sum) in the traced slice, in ms per engine
step in the slice. Also logs the slice's device time by the scopes of such a
model (``bench: device_by_expert_layer``), which ``spans.layer_of`` does not
know."""
import json

from benchmark import spans

WORDS = ("embed", "attn_mla", "mla_core", "kv_write", "mlp", "moe",
         "moe_router", "moe_experts", "moe_shared", "lm_head", "sample")


def read(run):
    sl, steps = spans.traced(run), run["counters"].get("trace_steps")
    ms = spans.self_ms_per_step(
        run, lambda e: "moe" in spans.scope_of(e).split("/"), "trace_steps")
    if ms is not None:
        table = sl.self_ns_by(lambda e: "/".join(
            w for w in WORDS if w in spans.scope_of(e).split("/")) or "(other)")
        print(f"bench: device_by_expert_layer (ms a step, {steps} steps): "
              + json.dumps({k: round(v / steps / 1e6, 3)
                            for k, v in table.items()}), flush=True)
    return ms
