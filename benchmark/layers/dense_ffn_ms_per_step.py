"""Self time of the device operations under the scope ``mlp`` (a layer's
dense feed-forward work: the norm before it, the SwiGLU's three products and
the residual add; in a shortcut-connected-MoE model both dense FFNs of every
layer, with the add of the expert layer's shortcut) in the traced slice, in
ms per engine step in the slice. On a model whose layers have no dense FFN
under that name the reader finds nothing."""
from benchmark import spans


def read(run):
    return spans.self_ms_per_step(
        run, lambda e: "mlp" in spans.scope_of(e).split("/"), "trace_steps")
