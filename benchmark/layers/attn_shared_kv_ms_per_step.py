"""Self time of the device operations under the scopes ``attn_global`` and
``attn_cross`` (the whole mixers of the layers that read the ONE pool that
keeps every token: the full layer, which also writes it, and the cross
layers after it) in the traced slice, in ms per engine step in the slice."""
from benchmark import spans


def read(run):
    return spans.self_ms_per_step(
        run, lambda e: {"attn_global", "attn_cross"}
        & set(spans.scope_of(e).split("/")), "trace_steps")
