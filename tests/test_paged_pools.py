"""The stacked K/V page pools stay one buffer through the serve step.

``forward_paged`` carries the pools ``[L, nkv, P, page, d]`` whole through
its scan over the layers; the ragged-paged-attention kernel copies a row's
pages of the layer it is given out of the stack; ``paged_kv_write`` writes
only the new tokens.
These tests hold the mechanism itself: the scan's signature, the kernels
against their references on stacks whose layers differ, the 4-D form as
the stack of one layer, and the outputs of ``forward_paged`` against the
ones the scanned-pool implementation gave for the same seeded case
(``tests/data/forward_paged_scanned_pools.npz``, written by the parent
commit of this change).
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import llama
from paddle_tpu.ops import pallas_ops

SAVED = os.path.join(os.path.dirname(__file__), "data",
                     "forward_paged_scanned_pools.npz")


@pytest.fixture
def interpret():
    old = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True
    yield
    pallas_ops._INTERPRET = old


# ---------------------------------------------------------------------------
# (a) the layer scan of forward_paged: pools in the carry, nowhere else
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub)


def _paged_case(quant, page, d, L=3, nkv=2, rep=2, P=5, R=3, Tc=4):
    cfg = llama.LlamaConfig(
        vocab_size=64, hidden_size=nkv * rep * d, intermediate_size=64,
        num_hidden_layers=L, num_attention_heads=nkv * rep,
        num_key_value_heads=nkv, max_position_embeddings=4 * page,
        dtype=jnp.float32)
    params = jax.eval_shape(functools.partial(llama.init_params, cfg),
                            jax.random.PRNGKey(0))
    sds = jax.ShapeDtypeStruct
    pool = sds((L, nkv, P, page, d), jnp.int8 if quant else cfg.dtype)
    scales = {"k_scales": sds((L, nkv, P), jnp.float32),
              "v_scales": sds((L, nkv, P), jnp.float32)} if quant else {}
    i32 = lambda *s: sds(s, jnp.int32)  # noqa: E731
    args = (params, i32(R, Tc), pool, pool, i32(R, 2), i32(R), i32(R))
    return cfg, args, scales, pool.shape


@pytest.mark.parametrize("case", ["dense", "int8", "dense-kernels",
                                  "int8-kernels"])
def test_the_layer_scan_carries_the_pools_and_never_scans_them(
        case, request):
    quant, kernels = case.startswith("int8"), case.endswith("kernels")
    if kernels:
        request.getfixturevalue("interpret")
    cfg, args, scales, pool_shape = _paged_case(
        quant, *((128, 128) if kernels else (8, 16)))
    L = pool_shape[0]
    layer_pool = (pool_shape[1:], (1,) + pool_shape[1:])
    jaxpr = jax.make_jaxpr(functools.partial(llama.forward_paged, cfg))(
        *args, **scales).jaxpr
    scans = [e for e in _walk(jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == L]
    assert len(scans) == 1, "one scan over the layers"
    (scan,) = scans
    nc, ncar = scan.params["num_consts"], scan.params["num_carry"]
    shapes = lambda vs: [tuple(v.aval.shape) for v in vs]  # noqa: E731
    carry_in = shapes(scan.invars[nc:nc + ncar])
    carry_out = shapes(scan.outvars[:ncar])
    xs, ys = shapes(scan.invars[nc + ncar:]), shapes(scan.outvars[ncar:])
    assert carry_in.count(pool_shape) == 2 == carry_out.count(pool_shape)
    if quant:
        assert carry_in.count(pool_shape[:3]) == 2
    for what, got in (("a scanned input", xs), ("a stacked output", ys),
                      ("a constant", shapes(scan.invars[:nc]))):
        for shape in got:
            assert shape != pool_shape and shape not in layer_pool \
                and shape != pool_shape[:3], f"{what} of shape {shape}"
    # inside the body nothing yields a layer's pool, and a whole pool
    # comes only out of the in-place writers
    body = scan.params["jaxpr"].jaxpr
    for eqn in _walk(body):
        for shape in shapes(eqn.outvars):
            assert shape not in layer_pool, f"{eqn.primitive.name}: {shape}"
            if shape == pool_shape:
                assert eqn.primitive.name in (
                    "scatter", "pallas_call", "pjit", "jit",
                    "closed_call"), eqn.primitive.name


# ---------------------------------------------------------------------------
# (b), (c) the layer-indexed kernel against the reference
# ---------------------------------------------------------------------------

def _stack_case(rep, Tc, quant, seed=0, L=3, R=4, nkv=2, d=128, P=12,
                page=128, Bmax=2):
    """Pools whose layers all differ, a ragged batch with a decode row,
    an idle row and (Tc > 1) a partial chunk."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.standard_normal((R, nkv, Tc * rep, d)),
                    jnp.float32)
    kp = rng.standard_normal((L, nkv, P, page, d)).astype(np.float32)
    vp = rng.standard_normal((L, nkv, P, page, d)).astype(np.float32)
    tbl = jnp.asarray((1 + rng.permutation(P - 1)[:R * Bmax])
                      .reshape(R, Bmax), jnp.int32)
    qlens = np.array([Tc, 1, 0, max(Tc - 1, 1)], np.int32)
    lens = jnp.asarray(np.array([200, 131, 0, 77], np.int32) + qlens)
    scales = ()
    if quant:
        def quantize(p):
            sc = np.maximum(np.abs(p).max(axis=(3, 4)), 1e-8) / 127.0
            return (np.round(p / sc[..., None, None]).astype(np.int8),
                    sc.astype(np.float32))
        (kp, ksc), (vp, vsc) = quantize(kp), quantize(vp)
        scales = (jnp.asarray(ksc), jnp.asarray(vsc))
    return q, jnp.asarray(kp), jnp.asarray(vp), tbl, lens, \
        jnp.asarray(qlens), scales


def _maxerr(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
@pytest.mark.parametrize("Tc", [1, 16])
@pytest.mark.parametrize("rep", [2, 1], ids=["gqa", "mha"])
def test_the_kernel_reads_the_layer_it_is_given(rep, Tc, quant, interpret):
    q, kp, vp, tbl, lens, qlens, scales = _stack_case(rep, Tc, quant)
    kw = dict(zip(("k_scales", "v_scales"), scales))
    layer = 2
    # the reference on that layer's own 4-D pool: no layer index in it
    kw_l = {k: v[layer] for k, v in kw.items()}
    want = pallas_ops._ragged_attention_jnp(
        q, kp[layer], vp[layer], tbl, lens, qlens, rep,
        *kw_l.values())
    got = pallas_ops._rpa_call(q, kp, vp, tbl, lens, qlens, rep=rep, layer=layer, **kw)
    assert _maxerr(got, want) < 2e-5
    # the same through the public entry with the layer traced, as the
    # layer scan hands it over
    traced = jax.jit(lambda l: pallas_ops.ragged_paged_attention(
        q, kp, vp, tbl, lens, qlens, rep=rep, layer=l, **kw))(
            jnp.int32(layer))
    assert _maxerr(traced, want) < 2e-5
    # the jnp body indexes the stack the same way
    ref5 = pallas_ops._ragged_attention_jnp(
        q, kp, vp, tbl, lens, qlens, rep, *kw.values(), layer=layer)
    assert _maxerr(ref5, want) == 0.0
    # and a wrong layer cannot pass: every other layer answers otherwise
    for other in (0, 1):
        wrong = pallas_ops._rpa_call(
            q, kp, vp, tbl, lens, qlens, rep=rep,
            layer=other, **kw)
        assert _maxerr(wrong, want) > 1e-2


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
def test_a_4d_pool_is_the_stack_of_one_layer(quant, interpret):
    q, kp, vp, tbl, lens, qlens, scales = _stack_case(2, 16, quant, seed=1)
    kw = dict(zip(("k_scales", "v_scales"), scales))
    stack0 = pallas_ops._rpa_call(q, kp, vp, tbl, lens, qlens, rep=2, layer=0, **kw)
    flat = pallas_ops._rpa_call(
        q, kp[0], vp[0], tbl, lens, qlens, rep=2,
        **{k: v[0] for k, v in kw.items()})
    assert _maxerr(flat, stack0) == 0.0
    ref = pallas_ops._ragged_attention_jnp(
        q, kp[0], vp[0], tbl, lens, qlens, 2,
        *(v[0] for v in kw.values()))
    assert _maxerr(flat, ref) < 2e-5


# ---------------------------------------------------------------------------
# the window: a first live page a row, a lower bound in the mask
# ---------------------------------------------------------------------------

WINDOW, RING = 300, 5     # ceil(300 / 128) + 2 ring pages a slot


def _window_case(rep, Tc, seed=0, L=2, R=5, nkv=2, d=128, page=128,
                 Bmax=12):
    """Pools behind a RING table (logical page b of slot r at pool page
    ``1 + r x RING + b % RING``, as ``models/phi4flash.py`` builds it): rows
    shorter than the window, exactly as long, idle, several pages longer
    (past the ring's wrap) and a partial chunk."""
    rng = np.random.RandomState(seed)
    P = 1 + R * RING
    q = jnp.asarray(rng.standard_normal((R, nkv, Tc * rep, d)), jnp.float32)
    kp, vp = (jnp.asarray(rng.standard_normal((L, nkv, P, page, d)),
                          jnp.float32) for _ in range(2))
    tbl = jnp.asarray(1 + np.arange(R)[:, None] * RING
                      + np.arange(Bmax)[None, :] % RING, jnp.int32)
    qlens = np.array([Tc, 1, 0, Tc, max(Tc - 1, 1)], np.int32)
    lens = np.array([100, WINDOW - 1, 0, 1100, 700], np.int32) + qlens
    return q, kp, vp, tbl, jnp.asarray(lens), jnp.asarray(qlens)


@pytest.mark.parametrize("Tc,rep", [(1, 4), (16, 4), (16, 1)],
                         ids=["Tr4", "Tr64", "Tr16"])
def test_the_windowed_kernel_walks_from_the_first_page_a_row_sees(
        Tc, rep, interpret):
    q, kp, vp, tbl, lens, qlens = _window_case(rep, Tc)
    want = pallas_ops._ragged_attention_jnp(
        q, kp, vp, tbl, lens, qlens, rep, layer=1, window=WINDOW)
    got = pallas_ops._rpa_call(q, kp, vp, tbl, lens, qlens, rep=rep,
                               layer=1, window=WINDOW)
    assert _maxerr(got, want) < 2e-5
    assert not bool(jnp.any(got[2])) and bool(jnp.all(jnp.isfinite(got)))
    traced = jax.jit(lambda l: pallas_ops.ragged_paged_attention(
        q, kp, vp, tbl, lens, qlens, rep=rep, layer=l, window=WINDOW))(
            jnp.int32(1))
    assert _maxerr(traced, want) < 2e-5
    # the window is no detail: without it the rows longer than it differ,
    # the row shorter than it and the row exactly as long do not
    full = pallas_ops._ragged_attention_jnp(q, kp, vp, tbl, lens, qlens, rep,
                                            layer=1)
    assert _maxerr(full[:2], want[:2]) == 0.0
    for r in (3, 4):
        assert _maxerr(full[r], want[r]) > 1e-2
    # what lies before a row's first page is never read: the ring pages of
    # row 3 that its walk does not reach may hold anything
    first = (int(lens[3]) - int(qlens[3]) - WINDOW + 1) // 128
    live = np.asarray(tbl)[3, first:-(-int(lens[3]) // 128)]
    dead = jnp.asarray(sorted(set(np.asarray(tbl)[3]) - set(live)))
    assert first == 6 and len(live) == 3 and len(dead) == RING - 3
    spoiled = pallas_ops._rpa_call(
        q, kp.at[:, :, dead].set(jnp.nan), vp.at[:, :, dead].set(jnp.nan),
        tbl, lens, qlens, rep=rep, layer=1, window=WINDOW)
    assert _maxerr(spoiled, got) == 0.0


def test_the_reference_counts_the_token_itself_in_its_window():
    """Position p sees the keys ``p - W < j <= p``: W keys with itself."""
    q, kp, vp, tbl, lens, qlens = _window_case(1, 1, page=8, d=16, Bmax=6)
    lens, qlens = jnp.asarray([20, 0, 0, 0, 0]), jnp.asarray([1, 0, 0, 0, 0])
    tbl = jnp.asarray(np.arange(1, 31).reshape(5, 6), jnp.int32)
    got = pallas_ops.ragged_paged_attention(q, kp, vp, tbl, lens, qlens,
                                            window=4)[0, :, 0]
    keys = kp[0, :, 1:4].reshape(2, 24, 16)[:, 16:20]     # positions 16..19
    vals = vp[0, :, 1:4].reshape(2, 24, 16)[:, 16:20]
    s = jnp.einsum("hd,hkd->hk", q[0, :, 0], keys) / 4.0
    want = jnp.einsum("hk,hkd->hd", jax.nn.softmax(s, -1), vals)
    assert _maxerr(got, want) < 1e-5


# sha256[:16] of str(jax.make_jaxpr(_rpa_call at layer 1)) on the cases of
# ``_stack_case`` under this suite's conftest (matmul precision "highest"):
# with ``window=None`` the kernel's program is what it was.  The two decode
# cases were written before the window came; the chunk at rep 2 (32 query
# rows) was written again when the dense walk took the few-row turn
# (``_rpa_short_rows``: a row of up to 8 tokens computes 16 rows)
RPA_JAXPR_BEFORE_THE_WINDOW = {
    (2, 16, False): "70c32ddcb7d9068e", (1, 1, True): "5ddd59b9eb035616",
    (2, 1, False): "7935b1cc0fcd1b3e"}


@pytest.mark.parametrize("case", sorted(RPA_JAXPR_BEFORE_THE_WINDOW),
                         ids=lambda c: f"rep{c[0]}-Tc{c[1]}-int8{c[2]}")
def test_without_a_window_the_kernel_traces_to_what_it_was(case, interpret):
    import hashlib
    rep, Tc, quant = case
    q, kp, vp, tbl, lens, qlens, scales = _stack_case(rep, Tc, quant)
    names = ("k_scales", "v_scales")[:len(scales)]

    def call(window):
        return str(jax.make_jaxpr(lambda q, kp, vp, *sc: pallas_ops._rpa_call(
            q, kp, vp, tbl, lens, qlens, rep=rep, layer=1, window=window,
            **dict(zip(names, sc))))(q, kp, vp, *scales))

    text = call(None)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == RPA_JAXPR_BEFORE_THE_WINDOW[case]
    assert call(WINDOW) != text


# ---------------------------------------------------------------------------
# the write of the new tokens: kernel against the row scatter
# ---------------------------------------------------------------------------

def _write_case(dtype, Tc, seed=0, L=3, nkv=2, P=17, page=128, d=128,
                R=5, Bmax=3):
    rng = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)  # noqa: E731
    pools = mk(L, nkv, P, page, d), mk(L, nkv, P, page, d)
    new = mk(R, Tc, nkv, d), mk(R, Tc, nkv, d)
    pages = (1 + rng.permutation(P - 1)).tolist() + [0] * (R * Bmax)
    tbl = np.asarray(pages[:R * Bmax], np.int32).reshape(R, Bmax)
    qlens = np.minimum(np.array([Tc, 1, 0, Tc, max(Tc - 1, 1)], np.int32),
                       Tc)
    # chunk starts: a page's first row, across a page boundary, an idle
    # row, across a tile boundary, late in the last block
    lens = np.array([0, 125, 40, 120, 250], np.int32) + qlens
    return pools, new, jnp.asarray(tbl), jnp.asarray(lens), \
        jnp.asarray(qlens)


@pytest.mark.parametrize("layer", [0, 2, "traced"])
@pytest.mark.parametrize("Tc", [1, 4, 16])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_write_kernel_writes_the_new_tokens_and_nothing_else(
        dtype, Tc, layer, interpret):
    pools, new, tbl, lens, qlens = _write_case(dtype, Tc)
    want = pallas_ops._pools_write_jnp(pools, new, tbl, lens, qlens,
                                    1 if layer == "traced" else layer)
    if layer == "traced":
        got = jax.jit(lambda l: pallas_ops.paged_kv_write(
            *pools, *new, tbl, lens, qlens, layer=l))(jnp.int32(1))
        layer = 1
    else:
        got = pallas_ops._pools_write_call(pools, new, tbl, lens, qlens,
                                        layer)
    nkv, written = pools[0].shape[1], int(jnp.sum(qlens))
    for old, a, b, fresh in zip(pools, got, want, new):
        assert a.shape == old.shape and a.dtype == old.dtype
        assert bool(jnp.all(a == b))
        rows = jnp.any(a != old, axis=-1)            # [L, nkv, P, page]
        assert int(jnp.sum(rows)) == written * nkv
        assert int(jnp.sum(rows[layer])) == written * nkv
        assert not bool(jnp.any(rows[:, :, 0]))      # the null page
    # the first request's chunk sits where its block table says
    pg = int(tbl[0, 0])
    assert bool(jnp.all(got[0][layer, :, pg, :Tc]
                        == new[0][0].transpose(1, 0, 2)))


def test_the_write_falls_back_to_the_row_scatter_off_tpu():
    assert not pallas_ops.kv_write_available((3, 2, 12, 128, 128),
                                             jnp.bfloat16)
    pools, new, tbl, lens, qlens = _write_case(jnp.float32, 4, page=8,
                                               d=16, seed=2)
    lens = jnp.minimum(lens, 20)
    got = pallas_ops.paged_kv_write(*pools, *new, tbl, lens, qlens, layer=1)
    want = pallas_ops._pools_write_jnp(pools, new, tbl, lens, qlens, 1)
    assert all(bool(jnp.all(a == b)) for a, b in zip(got, want))
    assert int(jnp.sum(jnp.any(got[0] != pools[0], axis=-1))) \
        == int(jnp.sum(qlens)) * 2


def test_unaligned_pages_or_heads_do_not_take_the_write_kernel(interpret):
    assert pallas_ops.kv_write_available((3, 2, 12, 128, 128), jnp.bfloat16)
    assert pallas_ops.kv_write_available((3, 2, 12, 8, 128), jnp.float32)
    assert not pallas_ops.kv_write_available((3, 2, 12, 8, 128),
                                             jnp.bfloat16)
    assert not pallas_ops.kv_write_available((3, 2, 12, 128, 64),
                                             jnp.bfloat16)


def test_both_kernels_lower_for_the_tpu_on_the_stacked_pools():
    """jax.export lowers the Mosaic kernels with no TPU attached: the
    layer-indexed attention and the aliased write, at bf16."""
    import jax.export
    R, nkv, rep, page, P, Bmax, D, L = 4, 2, 2, 128, 16, 4, 128, 3
    sds = jax.ShapeDtypeStruct
    pool = sds((L, nkv, P, page, D), jnp.bfloat16)
    tbl = jnp.asarray((1 + np.arange(R * Bmax) % (P - 1))
                      .reshape(R, Bmax), jnp.int32)
    lens = jnp.full((R,), 200, jnp.int32)
    old = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = False
    try:
        for Tc in (16, 1):
            qlens = jnp.full((R,), Tc, jnp.int32)

            def step(q, kn, vn, kp, vp, layer):
                kp, vp = pallas_ops._pools_write_call(
                    (kp, vp), (kn, vn), tbl, lens, qlens, layer)
                return pallas_ops._rpa_call(
                    q, kp, vp, tbl, lens, qlens, rep=rep, layer=layer), kp, vp

            new = sds((R, Tc, nkv, D), jnp.bfloat16)
            text = jax.export.export(jax.jit(step), platforms=["tpu"])(
                sds((R, nkv, Tc * rep, D), jnp.bfloat16), new, new, pool,
                pool, sds((), jnp.int32)).mlir_module()
            assert "_rpa_kernel" in text and "_kv_write_kernel" in text
            # both custom calls take the 5-D stacks as they stand
            calls = [ln for ln in text.splitlines()
                     if "tpu_custom_call" in ln]
            assert len(calls) == 2
            for ln in calls:
                assert ln.count(f"tensor<{L}x{nkv}x{P}x{page}x{D}xbf16>") \
                    >= 2, ln[:300]
    finally:
        pallas_ops._INTERPRET = old


# ---------------------------------------------------------------------------
# (d) forward_paged on the carried pools against the scanned-pool outputs
# ---------------------------------------------------------------------------

def seeded_forward_paged(quant):
    """Two steps of a seeded debug-width case (a prefill chunk per row,
    then a mixed step: next chunk, decode row, idle row, partial chunk);
    returns {name: array} of both steps' logits and pools.  Run on the
    parent commit to write ``SAVED``; run here to compare."""
    L, nkv, rep, d, P, page, R, Tc, Bmax = 2, 2, 2, 16, 10, 8, 4, 4, 3
    cfg = llama.LlamaConfig(
        vocab_size=96, hidden_size=nkv * rep * d, intermediate_size=96,
        num_hidden_layers=L, num_attention_heads=nkv * rep,
        num_key_value_heads=nkv, max_position_embeddings=64,
        dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    rng = np.random.RandomState(11)
    shape = (L, nkv, P, page, d)
    if quant:
        pools = (jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                 jnp.ones(shape[:3], jnp.float32),
                 jnp.ones(shape[:3], jnp.float32))
    else:
        pools = (jnp.zeros(shape, jnp.float32),
                 jnp.zeros(shape, jnp.float32))
    tbl = jnp.asarray([[3, 7, 1], [5, 2, 0], [9, 0, 0], [4, 8, 6]],
                      jnp.int32)
    out = {}
    steps = (([4, 4, 3, 4], [4, 4, 3, 4]),          # q_lens, seq_lens
             ([4, 1, 0, 2], [8, 5, 3, 6]))
    for n, (qlens, lens) in enumerate(steps):
        tokens = jnp.asarray(rng.randint(1, cfg.vocab_size, size=(R, Tc)),
                             jnp.int32)
        kp, vp, *scales = pools
        logits, pools = llama.forward_paged(
            cfg, params, tokens, kp, vp, tbl,
            jnp.asarray(lens, jnp.int32), jnp.asarray(qlens, jnp.int32),
            **dict(zip(("k_scales", "v_scales"), scales)))
        # rows past q_len are garbage by contract: keep the real ones
        real = np.arange(Tc)[None, :] < np.asarray(qlens)[:, None]
        out[f"logits{n}"] = np.where(real[..., None], np.asarray(logits),
                                     0.0)
        for name, p in zip(("k", "v", "k_scales", "v_scales"), pools):
            out[f"{name}{n}"] = np.asarray(p)
    return out


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
def test_forward_paged_equals_the_scanned_pool_implementation(quant):
    saved = np.load(SAVED)
    prefix = "int8/" if quant else "dense/"
    got = seeded_forward_paged(quant)
    assert {prefix + k for k in got} == \
        {k for k in saved.files if k.startswith(prefix)}
    for name, value in got.items():
        want = saved[prefix + name]
        assert value.shape == want.shape and value.dtype == want.dtype
        if name[0] in "kv" and "scales" not in name:
            # page 0 is the allocator's null page: the scanned-pool
            # implementation parked padding tokens there, this one
            # writes no padding at all and leaves it zero
            assert not value[:, :, 0].any(), name
            value, want = value[:, :, 1:], want[:, :, 1:]
        if quant and name.startswith("logits"):
            np.testing.assert_allclose(value, want, rtol=0, atol=1e-5)
        else:
            np.testing.assert_array_equal(value, want, err_msg=name)
