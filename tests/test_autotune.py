"""Autotune subsystem: cache behavior, config switch, persistence,
candidate selection. Reference analog: paddle/phi/kernels/autotune/
cache_test.cc + switch_autotune semantics."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import autotune, pallas_ops


@pytest.fixture(autouse=True)
def _fresh_cache():
    saved_cache = dict(autotune._CACHE)
    saved_enabled = autotune._ENABLED
    autotune._CACHE.clear()
    yield
    autotune._CACHE.clear()
    autotune._CACHE.update(saved_cache)
    autotune._ENABLED = saved_enabled


def test_tune_picks_fastest_and_caches():
    times = {"a": 3.0, "b": 1.0, "c": 2.0}
    calls = []

    def timer(cand):
        calls.append(cand)
        return times[cand]

    best = autotune.tune("op", ["k1"], ["a", "b", "c"], timer)
    assert best == "b"
    assert autotune.lookup("op", ["k1"]) == "b"
    # second tune short-circuits on the cache: no new measurements
    n = len(calls)
    assert autotune.tune("op", ["k1"], ["a", "b", "c"], timer) == "b"
    assert len(calls) == n


def test_tune_skips_disqualified_candidates():
    def timer(cand):
        if cand == "bad":
            raise RuntimeError("compile failed")
        return {"x": 2.0, "y": 1.0}[cand]

    assert autotune.tune("op", ["k"], ["bad", "x", "y"], timer) == "y"


def test_tune_all_disqualified_records_nothing():
    def timer(cand):
        raise RuntimeError("no")

    assert autotune.tune("op", ["k"], ["a"], timer) is None
    assert autotune.lookup("op", ["k"]) is None


def test_set_config_disables(tmp_path):
    autotune.set_config({"kernel": {"enable": False}})
    assert not autotune.enabled()
    assert autotune.tune("op", ["k"], ["a"], lambda c: 1.0) is None
    # JSON-file form, as the reference accepts
    p = tmp_path / "conf.json"
    p.write_text(json.dumps({"kernel": {"enable": True,
                                        "tuning_range": [1, 10]}}))
    autotune.set_config(str(p))
    assert autotune.enabled()


def test_cache_persistence_roundtrip(tmp_path):
    autotune.record("flash_attention", ["blocks", 2048, 128], (512, 256))
    path = str(tmp_path / "cache.json")
    autotune.save(path)
    autotune._CACHE.clear()
    autotune.load(path)
    assert autotune.lookup("flash_attention",
                           ["blocks", 2048, 128]) == (512, 256)


def test_block_config_consumes_tuned_entry():
    assert pallas_ops._block_config(2048, 128) == (256, 256)  # default
    autotune.record("flash_attention", ["blocks", 2048, 128], (512, 512))
    assert pallas_ops._block_config(2048, 128) == (512, 512)
    # dtype-keyed entry wins over the any-dtype fallback
    autotune.record("flash_attention",
                    ["blocks", 2048, 128, "bfloat16"], (1024, 1024))
    assert pallas_ops._block_config(2048, 128, jnp.bfloat16) == (1024, 1024)
    assert pallas_ops._block_config(2048, 128, jnp.float32) == (512, 512)
    # tuned config that does not tile S falls back to the default (512
    # does not divide 384, and 512x512 != the default, so a broken guard
    # would be caught here)
    autotune.record("flash_attention", ["blocks", 384, 128], (512, 512))
    assert pallas_ops._block_config(384, 128) == (256, 256)
    # Mosaic-illegal blocks in a (hand-edited) persisted cache are ignored
    autotune.record("flash_attention", ["blocks", 2304, 128], (192, 192))
    assert pallas_ops._block_config(2304, 128) == (256, 256)


def test_candidate_block_specs_mosaic_legal():
    """Every autotune candidate yields Mosaic-legal BlockSpecs for every
    shape it can be selected for (the r02 failure class, across the whole
    search space)."""
    for bq, bk in pallas_ops._BLOCK_CANDIDATES:
        for S in (2048, 4096):
            if S % bq or S % bk:
                continue
            specs = pallas_ops.flash_block_specs(64, S, 128, bq, bk)
            for kernel, groups in specs.items():
                for io in ("in", "out"):
                    for blk, arr in groups[io]:
                        assert pallas_ops.mosaic_block_legal(blk, arr), (
                            f"bq={bq} bk={bk} {kernel}/{io}: {blk} vs {arr}")


@pytest.mark.slow
def test_flash_nondefault_blocks_numerics():
    """Interpreter-mode numerical parity at a non-square tuned config
    (bq != bk exercises the generalized grid/loop arithmetic)."""
    import jax

    old = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True
    try:
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q, k, v = (jax.random.normal(kk, (1, 512, 2, 128), jnp.float32) * 0.5
                   for kk in ks)
        autotune.record("flash_attention", ["blocks", 512, 128], (128, 256))
        out = pallas_ops.causal_attention(q, k, v)
        ref = pallas_ops._attention_jnp(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        g = jax.grad(lambda a, b, c: jnp.sum(
            pallas_ops.causal_attention(a, b, c) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: jnp.sum(
            pallas_ops._attention_jnp(a, b, c) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for gf, grr, name in zip(g, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(grr),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"d{name} mismatch")
    finally:
        pallas_ops._INTERPRET = old


def test_save_after_partial_load_merges(tmp_path):
    """save() after a partial load() must not clobber on-disk entries for
    ops this process never re-tuned (the warmup-job workflow: one process
    tunes op A, another op B, both write the same cache file)."""
    path = str(tmp_path / "cache.json")
    # a prior process tuned opA/k1 and opB/k2
    autotune.record("opA", ["k1"], (1, 1))
    autotune.record("opB", ["k2"], (2, 2))
    autotune.save(path)
    # fresh process: loads nothing, tunes only opA/k3
    autotune._CACHE.clear()
    autotune.record("opA", ["k3"], (3, 3))
    autotune.save(path)
    autotune._CACHE.clear()
    autotune.load(path)
    assert autotune.lookup("opA", ["k1"]) == (1, 1)   # survived
    assert autotune.lookup("opB", ["k2"]) == (2, 2)   # survived
    assert autotune.lookup("opA", ["k3"]) == (3, 3)   # added
    # in-memory wins on a key conflict
    autotune._CACHE.clear()
    autotune.record("opA", ["k1"], (9, 9))
    autotune.save(path)
    autotune._CACHE.clear()
    autotune.load(path)
    assert autotune.lookup("opA", ["k1"]) == (9, 9)


def test_save_merge_tolerates_corrupt_file(tmp_path):
    path = tmp_path / "cache.json"
    path.write_text("{not json")
    autotune.record("op", ["k"], (1, 2))
    autotune.save(str(path))  # must not raise
    autotune._CACHE.clear()
    autotune.load(str(path))
    assert autotune.lookup("op", ["k"]) == (1, 2)


def test_lookup_chain_counts_one_hit_or_miss():
    autotune.record("op", ["specific"], (4, 4))
    h0, m0 = autotune._HITS, autotune._MISSES
    # fallback probe that misses then hits: exactly one hit total
    assert autotune.lookup_chain("op", [["missing"], ["specific"]]) == (4, 4)
    assert (autotune._HITS - h0, autotune._MISSES - m0) == (1, 0)
    # all probes miss: exactly one miss total
    assert autotune.lookup_chain("op", [["a"], ["b"], ["c"]]) is None
    assert (autotune._HITS - h0, autotune._MISSES - m0) == (1, 1)


def test_context_key_carries_dtype_device_jaxlib():
    key = autotune.context_key("bfloat16")
    assert len(key) == 3 and key[0] == "bfloat16"
    import jaxlib
    assert key[2] == jaxlib.__version__
    # different dtypes produce different keys -> distinct cache entries
    assert autotune.context_key("float32") != key


def test_legal_candidates_filters_and_disqualifies():
    calls = []

    def spec_fn(cand):
        calls.append(cand)
        if cand == "skip":
            return None
        # cand IS the block shape here; array huge so no equality escape
        return [(cand, (4096, 4096))]

    pool = ["skip", (8, 128), (1, 256), (8, 256), (8, 128)]
    got = autotune.legal_candidates(pool, spec_fn)
    assert got == [(8, 128), (8, 256)]       # (1, 256) is the r02 shape
    assert calls.count((8, 128)) == 1        # deduped before spec_fn


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S", [256, 384, 512, 2048, 2304, 4096])
def test_flash_candidates_always_legal_property(S, dtype):
    """Property: across a shapes x dtypes grid, the candidate generator
    yields ONLY configs whose every BlockSpec is Mosaic-legal and that
    tile S — illegal shapes are unrepresentable, not merely filtered at
    launch time."""
    bits = 8 * jnp.dtype(dtype).itemsize
    cands = pallas_ops.flash_candidates(S, 128, dtype)
    assert cands, f"no legal candidate at S={S}"
    for bq, bk in cands:
        assert S % bq == 0 and S % bk == 0
        specs = pallas_ops.flash_block_specs(8, S, 128, bq, bk)
        for kernel, groups in specs.items():
            for io in ("in", "out"):
                for blk, arr in groups[io]:
                    assert pallas_ops.mosaic_block_legal(
                        blk, arr, dtype_bits=bits), (
                        f"S={S} bq={bq} bk={bk} {kernel}/{io}: "
                        f"{blk} vs {arr}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(256, 256, 512), (512, 512, 1024),
                                   (2048, 2048, 5632)])
def test_fused_candidates_always_legal_property(shape, dtype):
    S, H, I = shape
    bits = 8 * jnp.dtype(dtype).itemsize
    for cands, spec_builder, dims in (
            (pallas_ops.fused_attn_candidates(1, S, H, 128, dtype),
             lambda c: pallas_ops.fused_attn_block_specs(8, S, H, 128, *c),
             "attn"),
            (pallas_ops.fused_mlp_candidates(1, S, H, I, dtype),
             lambda c: pallas_ops.fused_mlp_block_specs(8, S, H, I, *c),
             "mlp")):
        assert cands, f"no legal {dims} candidate at {shape}"
        for cand in cands:
            for kernel, groups in spec_builder(cand).items():
                for io in ("in", "out"):
                    for blk, arr in groups[io]:
                        assert pallas_ops.mosaic_block_legal(
                            blk, arr, dtype_bits=bits), (
                            f"{dims} {shape} {cand} {kernel}/{io}: "
                            f"{blk} vs {arr}")


def test_committed_bench_cache_short_circuits_tuning():
    """A hit in the committed .flash_autotune.json must return the
    winner without measuring (no device work)."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, ".flash_autotune.json")
    assert os.path.exists(path)
    autotune.load(path)
    old = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True  # satisfies the backend gate
    try:
        got = pallas_ops.tune_causal_attention(
            B=4, S=2048, H=16, D=128, dtype=jnp.bfloat16)
    finally:
        pallas_ops._INTERPRET = old
    assert tuple(got) == (512, 512)
    # and the train-path block selection consumes it
    assert pallas_ops._block_config(2048, 128, jnp.bfloat16) == (512, 512)
