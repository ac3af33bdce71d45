"""Window rings: the K/V of a sliding-window attention layer, kept a slot at a
time in a RING of pages that the window's newest tokens overwrite, beside
the engine's page pool (which holds what a layer keeps of EVERY token).
``models/phi4flash.py``, ``models/cohere2_moe.py`` and ``models/mellum.py``
keep their window layers here.

A window layer's pools are ``[Lw, nkv, 1 + R x Wp, page, d]``: for each of
its ``Lw`` window layers a ring of ``Wp = ceil(W / page) + 2`` pages a slot,
logical page b of slot r at pool page ``1 + r x Wp + b % Wp``, through a
table built inside the program from ``arange`` (nothing is uploaded); pool
page 0 is the null page that ``paged_kv_write`` parks its idle tiles on, as
the allocator's page 0 is in the engine's pool.

Why a ring of ``Wp`` pages is enough.  A step writes row r's chunk, positions
``p0 .. p0 + q - 1`` with ``q <= page``, and then its queries read the keys
``p0 - W + 1 .. p0 + q - 1``: at most ``W + page - 1`` positions in a row,
which touch at most ``ceil(W / page) + 2 = Wp`` logical pages in a row, and
``Wp`` pages in a row sit on ``Wp`` different ring pages.  So the page a
chunk overwrites when it enters logical page b (the ring page that held
page ``b - Wp``) is out of every live query's window: at W 512, page 128 the
chunk's first query, at ``p0 >= 128 b - 15``, needs keys ``> p0 - 512 >= 128
(b - 5) + 113``, and the page overwritten ends at ``128 (b - 5) - 1``.
The windowed kernel never reads before the first page a row's queries see
(``pallas_ops._rpa_walk``), and stale rows of the pages it does read are
masked as the future or as out of the window.

A model whose layers come in periods of window layers and then one layer of
every key (``cohere2_moe``, ``mellum``) runs them as ``scan_periods``: one
scan over the periods, each a scan over its window layers and then its
global layer, so the program holds two layer bodies whatever the depth.
Such a model keeps its global layers' K/V in the engine's pages and its
window layers' in rings (``init_cache``, ``cache_bytes``), and its layers'
attention is ``masked_attend`` on whole sequences and ``serve_step``'s on
the engine's step; the configuration names ``num_global_layers``,
``num_window_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim`` and ``sliding_window``.

A request that starts at position 0 (admission into a used slot,
preemption-replay, a rebuilt engine) needs no reset of its ring: it writes
every ring page before a query of its own reads it.  A prefix-cache hit
would skip tokens the ring never saw, and a rejected draft would have
overwritten ring entries that are still in the window: a model whose cache
holds rings declares ``rings`` in its ``SERVING`` and the engine refuses
both (``serving.LLMEngine``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import paged, protocol
from .step_layout import step_frame

__all__ = ["ring_pages", "init_rings", "ring_bytes", "ring_table",
           "window_counts", "scan_periods", "init_cache", "cache_bytes",
           "masked_attend", "serve_step"]


def ring_pages(window: int, page_size: int) -> int:
    """``Wp``: the pages of one slot's ring in a window layer."""
    return -(-window // page_size) + 2


def init_rings(layers: int, heads: int, slots: int, window: int,
               page_size: int, width: int, dtype) -> dict:
    """The zeroed K and V rings of ``layers`` window layers of ``heads``
    heads of ``width`` lanes, ``slots`` rings each, and the null page."""
    shape = (layers, heads, 1 + slots * ring_pages(window, page_size),
             page_size, width)
    return {"kw_pages": jnp.zeros(shape, dtype),
            "vw_pages": jnp.zeros(shape, dtype)}


def ring_bytes(layers: int, token_bytes: int, window: int,
               page_size: int) -> dict:
    """What the rings of ``layers`` window layers cost when a token's K and
    V take ``token_bytes`` in a layer: ``per_slot``, a slot's ``Wp`` pages
    whatever the length of the request in it, and ``fixed``, the null page
    whatever the engine's size."""
    page = layers * token_bytes * page_size
    return {"per_slot": ring_pages(window, page_size) * page, "fixed": page}


def ring_table(rows: int, cols: int, window: int, page_size: int,
               chunk: int):
    """The ``[rows, cols]`` table of the slots' rings (logical page b of
    row r at pool page ``1 + r x Wp + b % Wp``), made inside the program.
    A chunk longer than a page is refused: the ring holds a window and a
    chunk of at most a page."""
    Wp = ring_pages(window, page_size)
    if chunk > page_size:
        raise ValueError(
            f"a chunk of {chunk} tokens is longer than a page of "
            f"{page_size}: a ring of {Wp} pages a slot holds a window and a "
            "chunk of at most a page")
    return (1 + jnp.arange(rows, dtype=jnp.int32)[:, None] * Wp
            + jnp.arange(cols, dtype=jnp.int32)[None, :] % Wp)


def window_counts(seq_lens, q_lens, window: int) -> dict:
    """What ONE window layer reads in a step, from the host's arrays
    ``seq_lens, q_lens [R]``: ``window_kv_tokens``, over the fed rows the
    keys some query of the row sees (``min(seq_len, W + q_len - 1)``), and
    ``window_qk_pairs``, over the fed tokens the keys each sees (``min(p +
    1, W)`` at position p)."""
    seq, q = np.asarray(seq_lens, np.int64), np.asarray(q_lens, np.int64)
    t = np.arange(int(q.max(initial=0)))[None, :]
    seen = np.minimum((seq - q)[:, None] + t + 1, window)
    return {"window_kv_tokens": int(np.minimum(seq, window + q - 1)[q > 0]
                                    .sum()),
            "window_qk_pairs": int(seen[t < q[:, None]].sum())}


def scan_periods(layer, state, periods: int, period: int):
    """``state`` through ``periods`` periods of ``period`` layers each, the
    first ``period - 1`` window layers and the last a global one:
    ``layer(state, l, kind, at) -> state`` is layer ``l`` of the stacks, of
    ``kind`` "window" or "global", ``at`` its index among its kind's layers
    (all traced but ``kind``)."""
    S = period

    def one(state, p):
        def window(state, j):
            return layer(state, S * p + j, "window", (S - 1) * p + j), None

        state, _ = lax.scan(window, state,
                            jnp.arange(S - 1, dtype=jnp.int32))
        return layer(state, S * p + S - 1, "global", p), None

    state, _ = lax.scan(one, state, jnp.arange(periods, dtype=jnp.int32))
    return state


def init_cache(cfg, slots: int, num_pages: int, page_size: int, kv_dtype):
    """The cache of an engine with ``slots`` rows: the global layers' zeroed
    page pools and the window layers' rings (and their null page)."""
    protocol.refuse_narrow_pages(kv_dtype)
    nkv, d = cfg.num_key_value_heads, cfg.head_dim
    pool = (cfg.num_global_layers, nkv, num_pages, page_size, d)
    return {"k_pages": jnp.zeros(pool, kv_dtype),
            "v_pages": jnp.zeros(pool, kv_dtype),
            **init_rings(cfg.num_window_layers, nkv, slots,
                         cfg.sliding_window, page_size, d, kv_dtype)}


def cache_bytes(cfg, kv_dtype_bytes: int = 2, page_size: int = 128) -> dict:
    """What the cache costs: K/V bytes a token in the global layers, which
    keep every token; a slot's rings in the window layers (``Wp`` pages of
    ``page_size`` tokens each), whatever the length of the request in it;
    and, whatever the engine's size, the rings' null page (``fixed``)."""
    token = 2 * cfg.num_key_value_heads * cfg.head_dim * kv_dtype_bytes
    ring = ring_bytes(cfg.num_window_layers, token, cfg.sliding_window,
                      page_size)
    return {"per_token": cfg.num_global_layers * token, "scales_per_page": 0,
            "per_slot": ring["per_slot"], "fixed": ring["fixed"]}


def masked_attend(cfg, B: int, S: int):
    """``attend(kind, at, q [T, nh, d], k, v [T, nkv, d], pools) -> (out [T,
    nh d], pools)`` for ``B`` whole sequences of ``S`` tokens (``T = B x
    S``): plain masked attention, the window a mask in a window layer."""
    nh, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    rep = nh // nkv
    i = jnp.arange(S)
    causal = i[None, :] <= i[:, None]
    near = causal & (i[None, :] > i[:, None] - cfg.sliding_window)

    def attend(kind, at, q, k, v, pools):
        q = q.reshape(B, S, nkv, rep, d)
        k, v = k.reshape(B, S, nkv, d), v.reshape(B, S, nkv, d)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        s = jnp.where(near if kind == "window" else causal, s, -jnp.inf)
        a = jnp.einsum("bgrqk,bkgd->bqgrd",
                       jax.nn.softmax(s, axis=-1).astype(v.dtype), v)
        return a.reshape(B * S, nh * d), pools

    return attend


def serve_step(cfg, forward, tokens, cache, block_tables, seq_lens, q_lens,
               step_tokens=None, device_counts=False):
    """The engine's step (``protocol.ServingModel.forward_paged``) of such a
    model over ``cache`` (``init_cache``): ``forward(ids, pos, live, attend,
    pools) -> (logits [T, V], pools, counts)`` runs its layers on the step's
    flat tokens.  A global layer writes its K/V into the engine's pages
    through ``block_tables`` and reads them there; a window layer writes and
    reads its slot's ring through a table made here, with ``window=W``.  A
    request that starts at position 0 rewrites every ring page before it
    reads it, so admission into a used slot, preemption-replay and a rebuilt
    engine need no reset.  Padding tokens write no K/V."""
    R, Tc = tokens.shape
    table_w = ring_table(R, block_tables.shape[1], cfg.sliding_window,
                         cache["k_pages"].shape[3], Tc)
    lay, ids, pos, live = step_frame(tokens, seq_lens, q_lens, step_tokens)

    def attend(kind, at, q, k, v, pools):
        window = kind == "window"
        names = ("kw_pages", "vw_pages") if window else ("k_pages", "v_pages")
        table = table_w if window else block_tables
        kv = paged.write((pools[names[0]], pools[names[1]]), lay.rows(k),
                         lay.rows(v), table, seq_lens, q_lens, at)
        out = paged.attend(lay.rows(q), kv, table, seq_lens, q_lens,
                           layer=at,
                           window=cfg.sliding_window if window else None,
                           scope="attn_kernel")
        return lay.flat(out), dict(pools, **dict(zip(names, kv)))

    logits, pools, counts = forward(ids, pos, live, attend, dict(cache))
    return lay.result(logits, pools, counts if device_counts else None)
