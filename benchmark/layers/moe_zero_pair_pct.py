"""The share of a step's (token, expert) pairs that chose a zero-compute
expert, in %, over the engine steps of the traced slice: ``zero_pairs`` (the
device counts them, summed over the layers) over ``moe_pairs`` (fed tokens x
experts a token x layers), both arguments of the ``serve/engine_step`` span.
The engine keeps one step in flight, so a span carries the pairs of the step
it dispatched and the zero pairs of the step before it, which it fetched:
over a slice of steady steps the sums differ by a step at each end. Near
``Z / (E + Z)`` under seeded routing (a third for 256 of 768); what a router
trained to spend compute where it is needed, or a skewed traffic cell, moves.
On a program whose spans lack either counter the reader finds nothing."""
from benchmark import spans


def read(run):
    sl = spans.traced(run)
    steps = sl.step_args() if sl else []
    zero = sum(s["zero_pairs"] for s in steps if "zero_pairs" in s)
    pairs = sum(s["moe_pairs"] for s in steps if "zero_pairs" in s
                and "moe_pairs" in s)
    if not pairs:
        return None
    return 100.0 * zero / pairs
