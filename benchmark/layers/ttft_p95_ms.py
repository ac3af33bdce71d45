"""95th percentile, in ms, of the time from ``add_request`` to the first
``on_token`` over the requests whose first token fell inside the window, on
the benchmark's clock. A per-layer metric where the tail is too few samples
for a bound (``ttft_p95_ms.closed``: a hundred first tokens a window)."""
from benchmark import stats


def read(run):
    ttft = run["samples"].get("ttft_s")
    return stats.percentile(ttft, 95) * 1e3 if ttft else None
