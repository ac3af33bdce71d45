"""Self time of the device operations under the scope ``gmu`` (the gated
memory units: norm, gate projection, the product with the last Mamba
layer's scan output, output projection) in the traced slice, in ms per
engine step in the slice."""
from benchmark import spans


def read(run):
    return spans.self_ms_per_step(
        run, lambda e: "gmu" in spans.scope_of(e).split("/"), "trace_steps")
