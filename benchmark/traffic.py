"""The general generators: a traffic mix or a training job is a data file of
parameters (``traffic/<name>.json``) that these functions read.

Every draw comes from ``--seed``, so the same seed gives the same inputs.
What sets the *amount* of work — the set of prompt and output lengths, the
batch shape — is the same for every seed; the seed decides their order and
pairing, the clients' phases and every token id.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

SEED_MASK = (1 << 63) - 1


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator for ``stream`` under ``seed`` (any whole
    number, larger than 32 bits too)."""
    return np.random.default_rng(
        [int(seed) & SEED_MASK, *[ord(c) for c in stream]])


# -- training ---------------------------------------------------------------

def zipf_cdf(vocab: int, a: float) -> np.ndarray:
    """Cumulative distribution of ``p(rank) ~ 1 / rank**a`` over ``vocab``
    ranks: a unigram structure a model can learn, so the loss falls."""
    w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** a
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def train_batches(job: dict, vocab: int, seed: int):
    """Endless iterator of ``{"input_ids", "labels"}`` int32 ``[B, S]``
    batches for the job file ``job`` (``batch``, ``seq``, ``zipf_a``): token
    ids drawn from a Zipf distribution whose rank-to-token mapping is a
    permutation made from the seed; a new batch every step."""
    B, S = int(job["batch"]), int(job["seq"])
    cdf = zipf_cdf(vocab, float(job["zipf_a"]))
    token_of_rank = rng_for(seed, "zipf-perm").permutation(vocab).astype(
        np.int32)
    rng = rng_for(seed, "train-batches")
    while True:
        ranks = np.searchsorted(cdf, rng.random((B, S + 1)), side="left")
        ids = token_of_rank[np.minimum(ranks, vocab - 1)]
        yield {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


# -- serving ----------------------------------------------------------------

def lognormal_quantiles(dist: dict, n: int) -> list:
    """``n`` lengths, ascending: the ``(i + 0.5) / n`` quantiles of a
    log-normal with the ``median`` and ``sigma`` of ``dist``, clipped to
    ``[min, max]`` — the realised distribution is the stated one exactly,
    whatever the seed."""
    nd = statistics.NormalDist()
    mu, sigma = math.log(dist["median"]), float(dist["sigma"])
    xs = [int(round(math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))))
          for i in range(n)]
    return [min(max(x, int(dist["min"])), int(dist["max"])) for x in xs]


def dealt_in_rounds(xs: list, round_len: int, rng: np.random.Generator) -> list:
    """The ascending values ``xs`` in an order drawn from ``rng``, dealt
    so that every ``round_len`` consecutive values starting at a multiple
    of ``round_len`` span the whole distribution: round ``r`` of ``R =
    len(xs) / round_len`` holds ``xs[r], xs[r + R], xs[r + 2R], ...``. The
    rounds come in a shuffled order and each is shuffled within. A plain
    shuffle would give some seeds a window full of long requests and others
    none: the same sizes in another order, not other work."""
    R, rest = divmod(len(xs), round_len)
    if rest or not R:
        raise ValueError(f"{len(xs)} lengths do not make whole rounds of "
                         f"{round_len}")
    out = []
    for r in rng.permutation(R):
        one = xs[int(r)::R]
        out += [one[int(i)] for i in rng.permutation(round_len)]
    return out


def serve_lengths(mix: dict, seed: int) -> list:
    """The cycle of ``(prompt_len, output_len)`` pairs of the traffic file
    ``mix`` under ``seed``: the same ``n_lengths`` prompt lengths and the
    same output lengths for every seed (``lognormal_quantiles``), paired
    and ordered by the seed (``dealt_in_rounds`` of ``round`` requests,
    prompts and outputs dealt independently)."""
    n, round_len = int(mix["n_lengths"]), int(mix["round"])
    rng = rng_for(seed, "serve-lengths")
    prompts = dealt_in_rounds(lognormal_quantiles(mix["prompt"], n),
                              round_len, rng)
    outputs = dealt_in_rounds(lognormal_quantiles(mix["output"], n),
                              round_len, rng)
    return list(zip(prompts, outputs))


def start_fractions(mix: dict, seed: int) -> list:
    """How much of its first request each of a closed loop's ``clients``
    still has to do when the loop starts: the fractions ``(i + 0.5) /
    clients`` in an order drawn from the seed. Clients that all began whole
    requests at once would prefill together and then decode together for
    several request lifetimes; cut like this they start out of phase, as a
    loop that has run for long is."""
    n = int(mix["clients"])
    return [(int(i) + 0.5) / n
            for i in rng_for(seed, "serve-phases").permutation(n)]


class RequestStream:
    """Requests of a traffic file in the order of ``--seed``: lengths from
    ``serve_lengths`` (cycled; the first ``clients`` ones cut by
    ``start_fractions``), token ids of each prompt drawn from the seed —
    unique prompts, so no prefix is shared."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.lengths = serve_lengths(mix, seed)
        lo_p, lo_o = int(mix["prompt"]["min"]), int(mix["output"]["min"])
        self.first = [(max(lo_p, round(p * f)), max(lo_o, round(o * f)))
                      for (p, o), f in zip(self.lengths,
                                           start_fractions(mix, seed))]
        self.vocab = int(vocab)
        self.rng = rng_for(seed, "serve-prompts")
        self.n = 0

    def next(self) -> tuple:
        """``(prompt token ids, max_new_tokens)`` of the next request."""
        i, self.n = self.n, self.n + 1
        plen, olen = self.first[i] if i < len(self.first) else \
            self.lengths[i % len(self.lengths)]
        return self.rng.integers(0, self.vocab, plen).tolist(), olen
