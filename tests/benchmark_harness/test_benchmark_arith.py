"""The yardstick's own arithmetic: traffic generators, percentiles, FLOP
counts, the plain reference, and the trace reduction on a cut of a real
trace."""
import json
import math
import os
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib
from benchmark import flops, reference, stats, traffic, trace_reduce

BENCH = os.path.join(bench_testlib.REPO, "benchmark")
BIG_SEED = 2**31 + 11          # the driver's seeds pass 32 signed bits


def load(rel):
    with open(os.path.join(BENCH, rel)) as f:
        return json.load(f)


# -- traffic ------------------------------------------------------------------

def test_train_batches_repeat_for_a_seed_and_differ_between_seeds():
    job = load("traffic/train-2k.json")
    a = traffic.train_batches(job, 32256, BIG_SEED)
    b = traffic.train_batches(job, 32256, BIG_SEED)
    c = traffic.train_batches(job, 32256, BIG_SEED + 1)
    first, again, other = next(a), next(b), next(c)
    assert first["input_ids"].shape == (4, 2048)
    assert first["input_ids"].dtype == np.int32
    np.testing.assert_array_equal(first["input_ids"], again["input_ids"])
    np.testing.assert_array_equal(first["labels"][:, :-1],
                                  first["input_ids"][:, 1:])
    assert (first["input_ids"] != other["input_ids"]).mean() > 0.5
    assert (next(a)["input_ids"] != first["input_ids"]).mean() > 0.5
    assert 0 <= first["input_ids"].min() and first["input_ids"].max() < 32256


def test_train_tokens_follow_the_zipf_law_of_the_job_file():
    job = dict(load("traffic/train-2k.json"), batch=64, seq=1024)
    ids = next(traffic.train_batches(job, 1000, 5))["input_ids"]
    counts = np.sort(np.bincount(ids.ravel(), minlength=1000))[::-1]
    share = counts / counts.sum()
    h = sum(1.0 / r for r in range(1, 1001))
    assert share[0] == pytest.approx(1.0 / h, rel=0.05)        # rank 1
    assert share[9] == pytest.approx(0.1 / h, rel=0.15)        # rank 10


SEEDS = [3, 77, BIG_SEED, 2**32 + 15]


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_lengths_are_the_files_distribution_whatever_the_seed(seed):
    mix = load("traffic/serve-chat-closed.json")
    pairs = traffic.serve_lengths(mix, seed)
    assert pairs == traffic.serve_lengths(mix, seed) and len(pairs) == 528
    for i, dist in enumerate((mix["prompt"], mix["output"])):
        xs = [p[i] for p in pairs]
        # the same lengths for every seed: exactly the stated quantiles
        assert sorted(xs) == traffic.lognormal_quantiles(dist, 528)
        assert min(xs) >= dist["min"] and max(xs) <= dist["max"]
        assert statistics.median(xs) == pytest.approx(dist["median"], rel=0.01)
        # 95th percentile of the log-normal, where the clip does not reach
        want = dist["median"] * math.exp(1.6449 * dist["sigma"])
        if want < dist["max"]:
            assert stats.percentile(xs, 95) == pytest.approx(want, rel=0.03)
    assert max(p + o for p, o in pairs) <= mix["engine"]["max_model_len"]
    assert sum(p == mix["prompt"]["max"] for p, _ in pairs) > 1   # clipped


def test_the_seed_decides_order_pairing_and_phases():
    mix = load("traffic/serve-chat-closed.json")
    a, b = (traffic.serve_lengths(mix, s) for s in SEEDS[:2])
    assert a != b and sorted(a) != sorted(b)          # other order, pairing
    assert sum(x == y for x, y in zip(a, b)) < 20
    fa, fb = (traffic.start_fractions(mix, s) for s in SEEDS[:2])
    assert fa != fb and sorted(fa) == sorted(fb) == [
        (i + 0.5) / 48 for i in range(48)]
    assert fa == traffic.start_fractions(mix, SEEDS[0])


@pytest.mark.parametrize("seed", SEEDS)
def test_every_round_of_requests_spans_the_whole_distribution(seed):
    """Any 48 consecutive requests that start at a multiple of 48 hold one
    value from each 48th of both distributions, so no seed fills a window
    with long requests: their sums differ by a few percent, where a plain
    shuffle's differ by tens."""
    mix = load("traffic/serve-chat-closed.json")
    pairs = traffic.serve_lengths(mix, seed)
    for i, dist in enumerate((mix["prompt"], mix["output"])):
        ranked = traffic.lognormal_quantiles(dist, 528)
        sums = []
        for r in range(11):
            one = sorted(p[i] for p in pairs[48 * r:48 * (r + 1)])
            sums.append(sum(one))
            for k, x in enumerate(one):       # k-th smallest: k-th stratum
                assert ranked[11 * k] <= x <= ranked[11 * k + 10]
        assert max(sums) / min(sums) < 1.12
    shuffled = np.random.default_rng(seed).permutation(
        [p for p, _ in pairs]).reshape(11, 48).sum(1)
    assert shuffled.max() / shuffled.min() > 1.15


def test_rounds_must_divide_the_lengths():
    with pytest.raises(ValueError, match="whole rounds"):
        traffic.dealt_in_rounds(list(range(10)), 4,
                                np.random.default_rng(0))


def test_request_stream_repeats_for_a_seed_and_differs_between_seeds():
    mix = load("traffic/serve-chat-closed.json")
    a, b, c = (traffic.RequestStream(mix, 92544, s)
               for s in (BIG_SEED, BIG_SEED, 3))
    ra, rb, rc = ([s.next() for _ in range(600)] for s in (a, b, c))
    assert ra == rb
    assert [(len(p), n) for p, n in ra] != [(len(p), n) for p, n in rc]
    lengths = traffic.serve_lengths(mix, BIG_SEED)
    assert [(len(p), n) for p, n in ra[48:528]] == lengths[48:]
    # the first request of each of the 48 clients is cut to a fraction of
    # its length, a different one for each, so that they start out of phase
    fractions = traffic.start_fractions(mix, BIG_SEED)
    for (p, n), (plen, olen), f in zip(ra[:48], lengths, fractions):
        assert len(p) == max(32, round(plen * f))
        assert n == max(16, round(olen * f))
    # the cycle comes round whole, with other tokens: unique prompts
    assert (len(ra[528][0]), ra[528][1]) == lengths[0]
    assert (len(ra[576][0]), ra[576][1]) == (len(ra[48][0]), ra[48][1])
    assert ra[0][0] != rc[0][0] and ra[576][0] != ra[48][0]


# -- metric arithmetic ---------------------------------------------------------

def test_the_slowest_turns_come_with_their_parts_in_ms():
    from benchmark import harness
    turns = [{"wall": 0.1, "wait": 0.09}, {"wall": 2.5, "wait": 2.4},
             {"wall": 0.1, "wait": 0.08}, {"wall": 0.3, "wait": 0.1}]
    assert harness.slowest(turns, n=2) == [
        {"wall": 2500.0, "wait": 2400.0, "turn": 1},
        {"wall": 300.0, "wait": 100.0, "turn": 3}]


def test_stolen_cpu_time_is_seconds_that_only_grow():
    from benchmark import harness
    before = harness.host_steal_s()
    assert before is None or (isinstance(before, float) and before >= 0)
    if before is not None:
        assert 0 <= harness.host_steal_s() - before < 5


def test_the_heartbeat_goes_on_through_a_wait_and_ends_with_its_block():
    import time
    from benchmark import harness
    with harness.Heartbeat() as heart:
        t0 = time.perf_counter()
        time.sleep(0.25)              # a wait that lets other threads run
        quiet = heart.worst_s
        with pytest.raises(ZeroDivisionError):
            with harness.Heartbeat() as inner:
                1 / 0
        assert not inner._thread.is_alive()     # stopped on the way out too
    beat = heart.report(t0)
    assert 0 < quiet < 0.1            # 5 ms beats, on a busy test machine
    assert beat["longest_gap_ms"] >= round(quiet * 1e3, 2) - 0.01
    assert beat["at_s"] >= 0
    assert not heart._thread.is_alive()


@pytest.mark.parametrize("n", [1, 2, 20, 237])
@pytest.mark.parametrize("q", [50, 95, 99])
def test_percentile_is_numpy_s_linear_rule_on_raw_samples(n, q):
    xs = np.random.default_rng(n).lognormal(size=n).tolist()
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_summary_states_the_count():
    s = stats.summary([0.1, 0.2, 0.3, 0.4], scale=1e3)
    assert s["n"] == 4 and s["median"] == pytest.approx(250.0)
    assert s["max"] == pytest.approx(400.0)
    assert stats.summary([]) == {"n": 0}
    with pytest.raises(ValueError):
        stats.percentile([], 95)


HAND_COUNTS = {
    # matmul parameters by hand: L * (wq + wk + wv + wo + 3 * mlp) + lm_head
    "deepseek-coder-1.3b": (
        24 * (2048 * 2048 * 4 + 3 * 2048 * 5504) + 2048 * 32256,
        1346471936),
    "internlm2-1.8b": (
        24 * (2048 * 2048 * 2 + 2 * 2048 * 1024 + 3 * 2048 * 8192)
        + 2048 * 92544, 1889110016),
}


@pytest.mark.parametrize("name", sorted(HAND_COUNTS))
def test_flops_per_token_match_a_hand_count(name):
    cfg = load(f"configs/{name}.json")
    mm, total = HAND_COUNTS[name]
    assert flops.matmul_params(cfg) == mm
    # all parameters = matmul ones + the embedding table + the norms' gains
    h, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    assert mm + cfg["vocab_size"] * h + (2 * L + 1) * h == total
    assert cfg["parameters"] == total
    assert flops.train_flops_per_token(cfg, 2048) == \
        6 * mm + 6 * 2048 * h * L


def test_deepseek_is_8_29_gflop_a_token_at_2k():
    cfg = load("configs/deepseek-coder-1.3b.json")
    assert flops.train_flops_per_token(cfg, 2048) / 1e9 == \
        pytest.approx(8.286, abs=0.001)


def test_peaks_know_the_v5e_and_refuse_an_unknown_kind():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        flops.peaks("cpu")


# -- the plain reference -------------------------------------------------------

@pytest.mark.parametrize("nkv", [4, 2])
def test_reference_is_the_function_the_program_computes(nkv):
    from paddle_tpu.models import llama
    fields = dict(bench_testlib.DEBUG_CONFIG, num_key_value_heads=nkv)
    cfg = llama.LlamaConfig(**{k: fields[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads",
        "max_position_embeddings", "rms_norm_eps", "rope_theta")},
        dtype=jnp.float32, fused_blocks="off")
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    rows = [list(range(5, 45)), list(range(100, 117))]
    got = reference.logits(fields, params, rows)
    ids = np.zeros((2, 40), np.int32)
    ids[0], ids[1, :17] = rows[0], rows[1]
    want, _ = llama.forward_pure(cfg, params, jnp.asarray(ids))
    np.testing.assert_allclose(got[0], np.asarray(want)[0], atol=2e-5)
    np.testing.assert_allclose(got[1], np.asarray(want)[1, :17], atol=2e-5)
    # the loss and its gradient, over a batch of two sequences, against
    # the program's own loss_fn differentiated in float32
    batch = {"input_ids": ids[:, :16], "labels": ids[:, 1:17]}
    on_device = jax.tree_util.tree_map(jnp.asarray, batch)
    (_, ce), want = jax.value_and_grad(
        lambda p: llama.loss_fn(cfg, p, on_device), has_aux=True)(params)
    got_ce, got = reference.loss_and_grads(fields, params, batch)
    assert got_ce == pytest.approx(float(ce), abs=1e-5)
    assert sorted(got) == sorted(reference.GRAD_LEAVES)
    for k, g in got.items():
        w = want[k] if k in want else want["layers"][k]
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-6,
                                   rtol=1e-3)
    # a moment that is (1 - b1) times that gradient points the same way
    moment = jax.tree_util.tree_map(lambda g: 0.1 * g, want)
    errs = reference.direction_errors(moment, got)
    assert sorted(errs) == sorted(reference.GRAD_LEAVES)
    assert max(errs.values()) < 1e-3


@pytest.mark.parametrize("noise,lo,hi", [
    (0.0, 0.0, 1e-6), (0.01, 0.007, 0.013), (0.1, 0.07, 0.13)])
def test_direction_error_is_the_relative_size_of_what_does_not_belong(
        noise, lo, hi):
    """Noise of relative norm e turns the direction by about e, whatever
    the scale: a backward pass in a coarser type shows as a larger e."""
    rng = np.random.default_rng(0)
    grads = {"ln1": rng.normal(size=(3, 64)), "ln2": rng.normal(size=(3, 64)),
             "wv": rng.normal(size=(3, 64, 32)), "norm_f": rng.normal(size=64)}

    def noisy(g):
        n = rng.normal(size=g.shape)
        return 0.05 * (g + noise * n * np.linalg.norm(g) / np.linalg.norm(n))

    moment = {"layers": {k: noisy(grads[k]) for k in ("ln1", "ln2", "wv")},
              "norm_f": noisy(grads["norm_f"])}
    errs = reference.direction_errors(moment, grads)
    assert all(lo <= e <= hi for e in errs.values()), errs
    moment["norm_f"] = -moment["norm_f"]               # the wrong way round
    assert reference.direction_errors(moment, grads)["norm_f"] > 1.9


# -- the trace reduction ---------------------------------------------------------

def planes(ops, extra=None, slice_=(0, 1000)):
    host = {"python": [(trace_reduce.SLICE_NAME, slice_[0],
                        slice_[1] - slice_[0])]}
    dev = {"XLA Ops": ops, "XLA Modules": [("jit_step", 0, 900)],
           "Steps": [("0", 0, 900)]}
    return dict({"/host:CPU": host, "/device:TPU:0": dev}, **(extra or {}))


def test_busy_time_is_a_union_not_a_sum():
    out = trace_reduce.reduce_events(planes(
        [("a", 0, 400), ("copy", 100, 200), ("b", 600, 100)]))
    assert out["busy_s"] == pytest.approx(500e-9)     # not 700, not 1600
    assert out["window_s"] == pytest.approx(1000e-9)
    # "copy" is nested in "a": a's own time is what its child leaves
    assert out["device_ops"] == [["a", pytest.approx(200e-9)],
                                 ["copy", pytest.approx(200e-9)],
                                 ["b", pytest.approx(100e-9)]]
    assert out["idle_gaps"][0] == ["x1 b -> slice end", pytest.approx(300e-9)]
    assert out["idle_gaps"][1] == ["x1 a -> b", pytest.approx(200e-9)]


def test_events_are_clipped_to_the_slice():
    out = trace_reduce.reduce_events(planes(
        [("before", 0, 50), ("straddles", 80, 40), ("in", 200, 100),
         ("after", 900, 500)], slice_=(100, 950)))
    assert out["busy_s"] == pytest.approx((20 + 100 + 50) * 1e-9)
    assert out["events"] == 3
    assert "before" not in [name for name, _ in out["device_ops"]]


@pytest.mark.parametrize("case,match", [
    ("no tpu plane", "no TPU plane"),
    ("no ops line", "has no line"),
    ("empty ops line", "no event inside the slice"),
    ("no annotation", "0 host annotations"),
    ("two annotations", "2 host annotations"),
])
def test_the_reduction_fails_rather_than_print_a_zero(case, match):
    p = planes([("a", 0, 400)])
    if case == "no tpu plane":
        del p["/device:TPU:0"]
    elif case == "no ops line":
        del p["/device:TPU:0"]["XLA Ops"]
    elif case == "empty ops line":
        p["/device:TPU:0"]["XLA Ops"] = []
    elif case == "no annotation":
        p["/host:CPU"]["python"] = []
    else:
        p["/host:CPU"]["python"] *= 2
    with pytest.raises(trace_reduce.TraceError, match=match):
        trace_reduce.reduce_events(p)


def test_the_first_tpu_plane_is_the_one_with_the_lowest_number():
    p = planes([("a", 0, 400)], extra={
        "/device:TPU:10": {"XLA Ops": [("z", 0, 900)]},
        "/device:TPU:2": {"XLA Ops": [("y", 0, 800)]}})
    assert trace_reduce.reduce_events(p)["plane"] == "/device:TPU:0"
    del p["/device:TPU:0"]
    assert trace_reduce.reduce_events(p)["plane"] == "/device:TPU:2"


def test_self_times_of_nested_events_add_up_to_their_union():
    # a while loop spanning two iterations of a two-operation body
    events = [(0, 100, "while"), (5, 40, "x"), (40, 50, "y"),
              (55, 90, "x"), (90, 99, "y"), (120, 130, "after")]
    own = trace_reduce.self_times(events)
    assert dict((n, t) for t, n in own if n in ("while", "after")) == \
        {"while": 100 - 35 - 10 - 35 - 9, "after": 10}
    assert sum(t for t, _ in own) == trace_reduce.union_s(
        (a, b) for a, b, _ in events) == 110


def test_short_names_and_mosaic_kernels_from_hlo_instructions():
    long = ('%checkpoint.27 = bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)S(1)} '
            'custom-call(bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)S(1)} '
            '%pallas_call.113), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={bf16[4,2048,2048]{2,1,0}}')
    assert trace_reduce.short_name(long) == \
        "%checkpoint.27 custom-call tpu_custom_call"
    assert trace_reduce.short_name(
        "%while.6 = (s32[]{:T(128)}, bf16[4,2048]{1,0:T(8,128)(2,1)}) "
        "while((s32[]{:T(128)}) %tuple.39), condition=%c") == "%while.6 while"
    assert trace_reduce.short_name("benchmark_slice") == "benchmark_slice"
    out = trace_reduce.reduce_events(planes(
        [(long, 0, 300), ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
                          300, 100)]))
    assert out["mosaic_s"] == pytest.approx(300e-9)
    assert out["device_ops"][0] == [
        "%checkpoint.27 custom-call tpu_custom_call", pytest.approx(300e-9)]


@pytest.fixture(scope="module")
def real_cut():
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "fixtures", "train_trace_cut.json")) as f:
        cut = json.load(f)["planes"]
    return {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
            for p, lines in cut.items()}


def test_a_cut_of_the_first_real_trace_reduces_to_the_numbers_read_by_hand(
        real_cut):
    out = trace_reduce.reduce_events(real_cut)
    assert out["plane"] == "/device:TPU:0" and out["events"] == 202
    assert out["window_s"] == pytest.approx(0.150)
    assert out["busy_s"] == pytest.approx(0.148887764, abs=1e-9)
    assert out["mosaic_s"] == pytest.approx(0.14540373, abs=1e-8)
    # the line's durations add up to twice what it covers (the layer scan's
    # while spans its body), and all the device's lines to five times
    ops = real_cut["/device:TPU:0"]["XLA Ops"]
    assert sum(d for _, _, d in ops) > 1.9 * out["busy_s"] * 1e9
    assert sum(t for _, t in out["device_ops"]) == pytest.approx(
        out["busy_s"])
    assert out["device_ops"][0][0] == \
        "%closed_call.18 custom-call tpu_custom_call"
    assert out["idle_gaps"][0] == ["x1 slice start -> %fusion.165 fusion",
                                   pytest.approx(0.001112213)]
    assert all(len(name) <= 100 for name, _ in out["device_ops"])


def test_the_real_cut_with_an_empty_device_line_fails(real_cut):
    emptied = dict(real_cut)
    emptied["/device:TPU:0"] = dict(real_cut["/device:TPU:0"],
                                    **{"XLA Ops": []})
    with pytest.raises(trace_reduce.TraceError, match="no event inside"):
        trace_reduce.reduce_events(emptied)
    del emptied["/device:TPU:0"]
    with pytest.raises(trace_reduce.TraceError, match="no TPU plane"):
        trace_reduce.reduce_events(emptied)
