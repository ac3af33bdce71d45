"""Self time of the device operations under the scope ``mamba`` (a Mamba
layer's whole mixer: projections, ``ssm_conv``, ``ssm_scan``, gate) in the
traced slice, in ms per engine step in the slice. Also logs the slice's
device time by the scopes of such a model (``bench: device_by_mixer``),
which ``spans.layer_of`` does not know."""
import json

from benchmark import spans

WORDS = ("embed", "mamba", "ssm_conv", "ssm_scan", "attn", "kv_write", "mlp",
         "lm_head", "sample")


def read(run):
    sl, steps = spans.traced(run), run["counters"].get("trace_steps")
    ms = spans.self_ms_per_step(
        run, lambda e: "mamba" in spans.scope_of(e).split("/"), "trace_steps")
    if ms is not None:
        table = sl.self_ns_by(lambda e: "/".join(
            w for w in WORDS if w in spans.scope_of(e).split("/")) or "(other)")
        print(f"bench: device_by_mixer (ms a step, {steps} steps): "
              + json.dumps({k: round(v / steps / 1e6, 3)
                            for k, v in table.items()}), flush=True)
    return ms
