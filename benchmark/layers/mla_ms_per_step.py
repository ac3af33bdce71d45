"""Self time of the device operations under the scope ``attn_mla`` (a latent
attention layer's whole mixer: norm, projections, rotation, the absorption,
the latent's write, the attention over the paged latents, the up and output
projections) in the traced slice, in ms per engine step in the slice."""
from benchmark import spans


def read(run):
    return spans.self_ms_per_step(
        run, lambda e: "attn_mla" in spans.scope_of(e).split("/"),
        "trace_steps")
