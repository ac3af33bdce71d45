"""The latent attention's share of its roofline in the traced slice of a
model with two attention sublayers a layer (``"model": "longcat_flash"``), in
%: the least time the chip could take for the attention over the paged
latents of the engine steps wholly inside the slice
(``kernel_costs_longcat_flash.mla_step`` on the ``fed_tokens``,
``latent_kv_tokens`` and ``latent_qk_pairs`` of each step's
``serve/engine_step`` span, times ``2 x num_layers`` sublayers) over the self
time of those steps' device operations under the scope ``mla_core`` (scores,
softmax and the weighted sum of latents: the kernel, nothing of the
projections). The count is of the latent's own elements, not the 128-lane
tiles it is stored in nor the padded query rows, so the share cannot pass
100."""
from benchmark import kernel_costs_longcat_flash, scope_roofline


def read(run):
    return scope_roofline.roofline(
        run, "mla_core", ("fed_tokens", "latent_kv_tokens",
                          "latent_qk_pairs"),
        lambda s: kernel_costs_longcat_flash.mla_step(run["config"], s),
        "mla_attn_roofline.scmoe")
