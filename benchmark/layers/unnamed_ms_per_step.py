"""Self time of the slice's device operations that no name of the program
covers, in ms per engine step in the slice: those whose scope path, less
JAX's own components (``jit(..)``, ``while``, ``body``, ``cond``,
``closed_call``, ``checkpoint``, an ``einsum``'s subscripts, the final
primitive), holds nothing or
``layers`` alone. That is the layer loops' own work (a layer's weights
sliced out of their stack, the carries' copies) plus whatever a model forgot
to name; the rule is ``benchmark/step_budget.py``'s and reads the path alone.
Also logs ``bench: unnamed_ops`` (its five dearest instructions) and ``bench:
step_budget``: the device's busy time a step by the innermost name of every
operation, which adds up to it, beside the medians of the host's
``serve/schedule``, ``batch``, ``dispatch``, ``commit`` and ``fetch``."""
from benchmark import step_budget


def read(run):
    return step_budget.unnamed_ms_per_step(run)
