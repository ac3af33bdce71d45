"""What ``serving.LLMEngine`` asks a model for (a configuration's
``serving``, a ``ServingModel``), and what every model module reads its
configuration with: ``config_from_fields`` and ``preset``, which a module
binds to its class (``jamba.preset(name)``; docs/serving.md, "Adding a
model").
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ServingModel", "config_from_fields", "preset", "chip_share",
           "refuse_narrow_pages", "layer_at"]


@dataclasses.dataclass(frozen=True)
class ServingModel:
    """The engine's model: the step, the cache it runs on and what both
    weigh, then what the engine may be told besides, each defaulting to
    what a model that says nothing means (``serving/engine.py``'s module
    docstring says what the engine does with each)."""
    forward_paged: Callable
    init_cache: Callable
    cache_bytes: Callable
    param_count: Callable
    prepare_params: Callable = lambda cfg, params: params
    step_counts: Optional[Callable] = None
    device_counts: Tuple[str, ...] = ()
    recurrent_state: bool = False
    rings: bool = False


def config_from_fields(cls, fields: dict):
    """A ``cls`` from a ``config.json``-shaped dict: every key that is a
    field, ``dtype`` by name; a field ``<key>_published`` (the count that a
    chip's share is of) takes ``fields["published"][key]`` where the file
    gives it.  Other keys are not this model's."""
    names = {f.name for f in dataclasses.fields(cls)}
    kw = {k: v for k, v in fields.items() if k in names}
    published = fields.get("published", {})
    for name in names:
        key = name[:-len("_published")]
        if name.endswith("_published") and key in published:
            kw.setdefault(name, published[key])
    kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16")).type
    return cls(**kw)


def preset(cls, presets: dict, name: str, **overrides):
    """The ``cls`` of the preset ``name`` of ``presets``, fields overridden."""
    if name not in presets:
        raise KeyError(f"unknown {cls.__name__} preset {name!r}; "
                       f"available: {sorted(presets)}")
    return cls(**dict(presets[name], **overrides))


def chip_share(cfg, held: int, scored: int, count: str):
    """``cfg.experts_held``, the ids of the ``held`` routed experts (field
    ``count``) this device has of the ``scored`` the router scores, checked
    as ints, and ``vocab_size`` checked against ``vocab_size_published``."""
    ids = cfg.experts_held
    if ids is not None:
        ids = tuple(int(e) for e in ids)
        if len(ids) != held or not all(0 <= e < scored for e in ids):
            raise ValueError(
                f"experts_held names {len(ids)} experts; {count} says {held} "
                f"are here of the {scored} the router scores")
    elif scored != held:
        raise ValueError("a device that holds a share of the experts says "
                         "which (experts_held)")
    if cfg.vocab_size > (cfg.vocab_size_published or cfg.vocab_size):
        raise ValueError("a slice of the vocabulary is no larger than it")
    return ids


def refuse_narrow_pages(kv_dtype) -> None:
    """Pages of fewer than two bytes an element hold int8 values that need
    scales beside them, which only ``models/llama.py``'s step writes."""
    if jnp.dtype(kv_dtype).itemsize < 2:
        raise ValueError(
            f"kv_dtype {jnp.dtype(kv_dtype)} pages need the per-page scale "
            "pools that only models/llama.py's step writes")


def layer_at(stack, l):
    """Layer ``l`` (traced) of a stack of per-layer leaves, sliced from the
    whole stack: a run of layers scans the one stack by index, copying none."""
    return jax.tree_util.tree_map(
        lambda w: lax.dynamic_index_in_dim(w, l, 0, keepdims=False), stack)
