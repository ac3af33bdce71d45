"""The two device scopes of PR 35 are names and nothing else.

``step_layout`` (inside ``StepLayout``) and ``ssm_proj`` (the Mamba mixers'
per-token stretches) are ``jax.named_scope``: for each of the four serving
models at debug width, both buckets, the lowered program carries them where
PERF.md says, every other name reads as it read, and with the two scopes
made no-ops the lowered text is the same once locations are stripped."""
import contextlib
import functools
import re

import jax
import pytest

from paddle_tpu import serving
from paddle_tpu.models import deepseek_v2, jamba, llama, phi4flash
from paddle_tpu.serving.scheduler import Scheduler

NEW = ("step_layout", "ssm_proj")
MODELS = {"llama": (llama, "llama-debug"),
          "jamba": (jamba, "jamba-debug"),
          "phi4flash": (phi4flash, "phi4flash-debug"),
          "deepseek_v2": (deepseek_v2, "deepseek-v2-debug")}
CHUNK = 8


@functools.lru_cache(maxsize=None)
def engine(name):
    """A debug-width engine whose mixed step computes a budget of flat
    tokens (24 for 8 rows of 8), so that ``StepLayout`` gathers."""
    mod, preset = MODELS[name]
    cfg = mod.preset(preset)
    eng = serving.LLMEngine(cfg, mod.init_params(cfg, jax.random.PRNGKey(0)),
                            max_running=8, max_model_len=256, page_size=16,
                            chunk=CHUNK)
    eng.scheduler = Scheduler(eng.kv, max_running=eng.max_running,
                              chunk=eng.chunk,
                              max_model_len=eng.max_model_len, step_tokens=24)
    return eng


_LOC_DEF = re.compile(r"^(#loc\d+) = loc\((.*)\)$", re.M)
_LOC_USE = re.compile(r"\s*loc\((?:[^()]|\([^()]*\))*\)")
_OP = re.compile(r"(call @[\w.]+|\"?stablehlo\.\w+\"?|func\.call)")


class Lowered:
    """A lowered program's text with its locations read: ``ops`` is every
    operation of the program's own functions (``main`` and the layer
    loops' ``closed_call`` bodies; a jitted helper such as ``jnp.take`` is
    the ``call`` into it, which carries the caller's scopes) in order as
    ``(operation, scope path, source)``, the source ``file::function`` of
    the innermost Python frame; ``bare`` the text with every location
    stripped."""

    def __init__(self, text):
        defs = dict(_LOC_DEF.findall(text))

        def path_of(ref):
            m = re.match(r'"([^"]*)"\(', defs.get(ref, ""))
            return m.group(1) if m else ""

        def source_of(ref, depth=0, func=""):
            body = defs.get(ref, "")
            m = re.match(r'"([^"]*\.py)":', body)
            if m or depth > 20:
                return f"{m.group(1) if m else ''}::{func}"
            name = re.match(r'"([^"]*)"\(#loc\d+\)$', body)
            inner = re.search(r"#loc\d+", body)
            return source_of(inner.group(0), depth + 1,
                             name.group(1) if name else func) \
                if inner else f"::{func}"

        self.ops, own = [], False
        for line in text.splitlines():
            func = re.match(r"\s*func\.func \w+ @(\w+)", line)
            if func:
                own = func.group(1).startswith(("main", "closed_call"))
                continue
            use = re.search(r"loc\((#loc\d+)\)\s*$", line)
            op = _OP.search(line)
            if own and use and op and not line.startswith("#loc"):
                self.ops.append((op.group(1).strip('"'),
                                 path_of(use.group(1)),
                                 source_of(use.group(1))))
        self.bare = "\n".join(
            _LOC_USE.sub("", line) for line in text.splitlines()
            if not line.startswith("#loc"))


def lower(name, Tc):
    return Lowered(engine(name)._lower(Tc).as_text(debug_info=True))


def under(path, word):
    return word in path.split("/")


def without_new(path):
    return "/".join(p for p in path.split("/") if p not in NEW)


@pytest.mark.parametrize("Tc", [CHUNK, 1])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_new_scopes_name_operations_and_change_none(name, Tc, monkeypatch):
    real = lower(name, Tc)

    # every operation traced in StepLayout is under step_layout, and no
    # other; the mixed bucket's layout gathers (jnp.take).  (The module's
    # ``step_frame`` computes a step's positions outside the scope, as the
    # models did before it: StepLayout's own maps are what is named.)
    from_layout = [(op, path) for op, path, src in real.ops
                   if "models/step_layout.py::StepLayout." in src]
    assert from_layout and all(under(p, "step_layout")
                               for _, p in from_layout)
    assert sum(under(p, "step_layout") for _, p, _ in real.ops) \
        == len(from_layout)
    takes = [p for op, p in from_layout if op.startswith("call @_take")]
    assert (len(takes) >= 4) if Tc > 1 else not takes

    mixers = [(op, p) for op, p, _ in real.ops if under(p, "mamba")]
    assert bool(mixers) == (name in ("jamba", "phi4flash"))
    if mixers:
        # the mixer's four matmuls, in every traced copy of a Mamba layer
        dots = [p for op, p in mixers if op == "stablehlo.dot_general"
                and not under(p, "ssm_scan")]
        assert dots and len(dots) % 4 == 0
        assert all(under(p, "ssm_proj") for p in dots)
        # the four names are disjoint, and what is under none of them is
        # the residual add and nothing else
        four = ("ssm_proj", "ssm_conv", "ssm_scan", "step_layout")
        assert all(sum(under(p, w) for w in four) <= 1 for _, p in mixers)
        rest = {(op, p.rsplit("/", 1)[-1]) for op, p in mixers
                if not any(under(p, w) for w in four)}
        assert rest == {("stablehlo.add", "add")}
    assert not any(under(p, "ssm_proj") for _, p, _ in real.ops
                   if not under(p, "mamba"))

    # with the two scopes made no-ops: the same program, and every other
    # name where it was (ssm_conv and ssm_scan hold what they held)
    scope = jax.named_scope
    monkeypatch.setattr(jax, "named_scope", lambda n: (
        contextlib.nullcontext() if n in NEW else scope(n)))
    plain = lower(name, Tc)
    monkeypatch.undo()
    assert not any(under(p, w) for _, p, _ in plain.ops for w in NEW)
    assert plain.bare == real.bare
    assert [(op, without_new(p)) for op, p, _ in real.ops] \
        == [(op, p) for op, p, _ in plain.ops]
    for word in ("ssm_conv", "ssm_scan"):
        assert [x for x in real.ops if under(x[1], word)] \
            == [x for x in plain.ops if under(x[1], word)]
