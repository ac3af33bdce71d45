"""The model protocol ``serving.LLMEngine`` asks a configuration for
(``cfg.serving``), held to its own arithmetic for everything the engine
serves today. The test to run first for a new ``model_config``: add a row
to ``MODELS``.

What is checked: the six members are there; ``init_cache``'s leaves weigh
exactly what ``cache_bytes`` says (a token, a page's scales, a slot, and
where a model says so a ``fixed`` amount, at the engine's page size); ``forward_paged`` returns a cache of
the tree, shapes and dtypes it was given, which is what donating the
cache into the step relies on; and ``kv_bytes_per_token``,
``plan_capacity`` and the engine's reservations in ``profiler.xmem``
agree with the bytes of the live cache.
"""
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import serving
from paddle_tpu.models import deepseek_v2, jamba, llama, phi4flash
from paddle_tpu.ops import pallas_ops
from paddle_tpu.profiler import xmem

SLOTS, PAGES, PAGE, CHUNK = 3, 9, 16, 4

# id -> (module, preset, kv_dtype the engine is given)
MODELS = {
    "llama-bf16": (llama, "llama-debug", None),
    "llama-int8": (llama, "llama-debug", "int8"),
    "jamba": (jamba, "jamba-debug", None),
    "phi4flash": (phi4flash, "phi4flash-debug", None),
    "deepseek_v2": (deepseek_v2, "deepseek-v2-debug", None),
}


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True
    yield
    pallas_ops._INTERPRET = old


@pytest.fixture(params=sorted(MODELS))
def served(request):
    """(cfg, params, the page dtype, the engine's ``kv_dtype`` argument)."""
    module, preset, kv_dtype = MODELS[request.param]
    cfg = module.preset(preset)
    params = module.init_params(cfg, jax.random.PRNGKey(0))
    page_dtype = jnp.dtype(jnp.int8 if kv_dtype == "int8" else cfg.dtype)
    return cfg, params, page_dtype, kv_dtype


def _nbytes(tree):
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


def _says(layout, pages, page, slots):
    return (layout["per_token"] * page * pages
            + layout["scales_per_page"] * pages + layout["per_slot"] * slots
            + layout.get("fixed", 0))


def test_the_protocol_is_whole_and_the_cache_weighs_what_it_says(served):
    cfg, _, page_dtype, _ = served
    model = cfg.serving
    for member in ("forward_paged", "init_cache", "cache_bytes",
                   "param_count", "prepare_params"):
        assert callable(getattr(model, member)), member
    assert isinstance(model.recurrent_state, bool)
    layout = model.cache_bytes(cfg, page_dtype.itemsize, PAGE)
    assert set(layout) - {"fixed"} == {"per_token", "scales_per_page",
                                       "per_slot"}
    assert (layout["per_slot"] > 0) == model.recurrent_state
    cache = model.init_cache(cfg, SLOTS, PAGES, PAGE, page_dtype)
    assert _nbytes(cache) == _says(layout, PAGES, PAGE, SLOTS)
    # and at another size: no term hides in a constant
    cache = model.init_cache(cfg, SLOTS + 2, PAGES + 4, 2 * PAGE, page_dtype)
    layout = model.cache_bytes(cfg, page_dtype.itemsize, 2 * PAGE)
    assert _nbytes(cache) == _says(layout, PAGES + 4, 2 * PAGE, SLOTS + 2)


def test_forward_paged_hands_back_the_cache_it_was_given(served):
    cfg, params, page_dtype, _ = served
    model = cfg.serving
    params = model.prepare_params(cfg, params)
    cache = model.init_cache(cfg, SLOTS, PAGES, PAGE, page_dtype)
    tokens = jnp.arange(SLOTS * CHUNK, dtype=jnp.int32).reshape(
        SLOTS, CHUNK) % cfg.vocab_size
    tables = jnp.arange(1, 1 + 2 * SLOTS, dtype=jnp.int32).reshape(SLOTS, 2)
    # a prefill chunk, a shorter one, and an idle row
    q_lens = jnp.asarray([CHUNK, 2, 0], jnp.int32)
    logits, out = model.forward_paged(cfg, params, tokens, cache, tables,
                                      q_lens, q_lens)
    assert logits.shape == (SLOTS, CHUNK, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert (jax.tree_util.tree_structure(out)
            == jax.tree_util.tree_structure(cache))
    for got, given in zip(jax.tree_util.tree_leaves(out),
                          jax.tree_util.tree_leaves(cache)):
        assert (got.shape, got.dtype) == (given.shape, given.dtype)


def test_capacity_arithmetic_agrees_with_the_live_cache(served):
    cfg, params, page_dtype, kv_dtype = served
    layout = cfg.serving.cache_bytes(cfg, page_dtype.itemsize, PAGE)
    assert (serving.kv_bytes_per_token(cfg, page_dtype.itemsize)
            == layout["per_token"])
    plan = serving.plan_capacity(cfg, hbm_bytes=1 << 30, page_size=PAGE,
                                 kv_dtype_bytes=page_dtype.itemsize)
    assert plan["kv_bytes_per_token"] == layout["per_token"]
    assert plan["state_bytes_per_slot"] == layout["per_slot"]
    assert plan["page_bytes"] == (layout["per_token"] * PAGE
                                  + layout["scales_per_page"])
    eng = serving.LLMEngine(cfg, params, max_running=SLOTS, chunk=CHUNK,
                            page_size=PAGE, num_pages=PAGES,
                            max_model_len=4 * PAGE, kv_dtype=kv_dtype)
    try:
        held = {r["name"]: r for r in xmem.reservations()}
        pages = held["serving.kv_pages"]
        assert pages["bytes_per_token"] == layout["per_token"]
        state = held["serving.state"]["bytes"] if layout["per_slot"] else 0
        assert pages["bytes"] + state == _nbytes(eng._pools)
        assert _nbytes(eng._pools) == _says(layout, PAGES, PAGE, SLOTS)
    finally:
        eng.shutdown()
    assert not any(r["name"] == "serving.kv_pages"
                   for r in xmem.reservations())
