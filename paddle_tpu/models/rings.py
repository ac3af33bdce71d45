"""Window rings: the K/V of a sliding-window attention layer, kept a slot at a
time in a RING of pages that the window's newest tokens overwrite, beside
the engine's page pool (which holds what a layer keeps of EVERY token).
``models/phi4flash.py`` and ``models/cohere2_moe.py`` keep their window
layers here.

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

A request that starts at position 0 (admission into a used slot,
preemption-replay, a rebuilt engine) needs no reset of its ring: it writes
every ring page before a query of its own reads it.  A prefix-cache hit
would skip tokens the ring never saw, and a rejected draft would have
overwritten ring entries that are still in the window: a model whose cache
holds rings declares ``rings`` in its ``SERVING`` and the engine refuses
both (``serving.LLMEngine``).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = ["ring_pages", "init_rings", "ring_bytes", "ring_table",
           "window_counts"]


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
