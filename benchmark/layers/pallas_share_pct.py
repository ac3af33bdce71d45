"""Share of device busy time, in the traced slice, spent in Mosaic (Pallas)
kernels: the self time of the events that are ``tpu_custom_call`` custom
calls (``benchmark/trace_reduce.py``) over the busy time. One reader for
``pallas_share_pct.train`` and ``pallas_share_pct.serve``, which differ in
their cells and in the end-to-end metric they move. Nothing to read where
the cell's lowered programs hold no Mosaic kernel."""


def read(run):
    if not run["trace"] or not run["kernels"]:
        return None
    return 100.0 * run["trace"]["mosaic_s"] / run["trace"]["busy_s"]
