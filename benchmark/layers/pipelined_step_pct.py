"""Share of the engine steps in the traced slice that were dispatched behind
another step still on the device: ``100 x`` the sum of ``in_flight`` (0 or 1,
an argument of ``serve/engine_step``) over the spans wholly inside the slice
that fed the device, over their number, in %; ``None`` where no span carries
the argument. Under 100 the engine fell back to fetch-before-dispatch
(``spec=``, ``prefix_cache=True``, page pressure: ``pipeline_drains.*`` in
``serving_stats()``) and keeps the host's gap."""
from benchmark import spans


def read(run):
    sl = spans.traced(run)
    steps = [s for s in (sl.step_args() if sl else []) if "in_flight" in s]
    return 100.0 * sum(s["in_flight"] for s in steps) / len(steps) \
        if steps else None
