"""Share of the step's token slots that held a fed token: ``fed_tokens``
(sum of ``q_len``) over ``slot_tokens`` (``max_running x Tc``), both
arguments of the ``serve/engine_step`` spans, summed over the engine steps
wholly inside the traced slice, in %."""
from benchmark import spans


def read(run):
    sl = spans.traced(run)
    steps = sl.step_args() if sl else []
    slots = sum(s["slot_tokens"] for s in steps)
    return 100.0 * sum(s["fed_tokens"] for s in steps) / slots \
        if slots else None
