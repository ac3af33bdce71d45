"""Self time of the device operations under the scope ``attn_window`` (a
window layer's whole mixer: norm, projection, the write into its slot's
ring, the windowed RPA kernel, the differential combination, the output
projection) in the traced slice, in ms per engine step in the slice. Also
logs the slice's device time by the scopes of such a model and, for the two
Pallas kernels, by the kind of layer that called them (``bench:
device_by_layer_kind``), which ``spans.layer_of`` does not know."""
import json

from benchmark import spans

WORDS = ("embed", "mamba", "ssm_conv", "ssm_scan", "gmu", "attn_window",
         "attn_global", "attn_cross", "kv_write", "mlp", "lm_head", "sample")


def read(run):
    sl, steps = spans.traced(run), run["counters"].get("trace_steps")
    ms = spans.self_ms_per_step(
        run, lambda e: "attn_window" in spans.scope_of(e).split("/"),
        "trace_steps")
    if ms is not None:
        def key(e):
            words = [w for w in WORDS if w in spans.scope_of(e).split("/")]
            return "/".join(words + [spans.kernel_of(e) or ""]).strip("/") \
                or "(other)"
        print(f"bench: device_by_layer_kind (ms a step, {steps} steps): "
              + json.dumps({k: round(v / steps / 1e6, 3)
                            for k, v in sl.self_ns_by(key).items()}),
              flush=True)
    return ms
