"""Self time of the device operations under the scope ``ssm_scan`` (the
selective scan of every Mamba layer: the state's update and its read-out)
in the traced slice, in ms per engine step in the slice."""
from benchmark import spans


def read(run):
    return spans.self_ms_per_step(
        run, lambda e: "ssm_scan" in spans.scope_of(e).split("/"),
        "trace_steps")
