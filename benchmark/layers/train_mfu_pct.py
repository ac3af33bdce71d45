"""Model FLOP/s utilisation: the forward + backward operations a trained
token requires (``benchmark/flops.py``; recompute not counted) times tokens
per second, over the bf16 peak of this ``device_kind``."""
from benchmark import flops


def read(run):
    rate = run["end_to_end"].get("train_tokens_per_s")
    if rate is None:
        return None
    per_token = flops.train_flops_per_token(run["config"],
                                            run["counters"]["seq"])
    peak = flops.peaks(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * per_token * rate / peak
