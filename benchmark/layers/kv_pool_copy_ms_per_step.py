"""Self time, in the traced slice, of the non-Mosaic device operations whose
result (for a ``copy-start``/``copy-done``, an element of it) has the shape
of the engine's stacked K or V page pool or of one layer's pool
(``kernel_costs.pool_shapes``, from the configuration and the traffic
file's ``engine`` arguments): the slices, relayouts and write-backs of
whole pools that the step makes around its scan over the layers, and the
in-place scatter of the step's new tokens among them. Needs no span or
scope. In ms per engine step in the slice."""
from benchmark import kernel_costs, spans


def read(run):
    shapes = kernel_costs.pool_shapes(run["config"], run["traffic"])
    return spans.self_ms_per_step(
        run, lambda e: spans.is_pool_copy(e, shapes), "trace_steps")
