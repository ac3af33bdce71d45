"""Level-3 lint, part (b): the SPMD collective-consistency checker.

A multi-host TPU program deadlocks the moment two ranks disagree about
which collective comes next.  PR 7's runtime health layer detects that
hang *after* it happens; this module proves the absence of the whole
divergence class at trace time, by abstractly executing a jaxpr per
rank-group:

* every collective is reduced to an event ``(primitive, axis names,
  dtype)`` — the wire signature that must match across ranks;
* control flow is walked structurally: ``pjit`` / ``remat`` /
  ``custom_*`` bodies are inlined (the checker is interprocedural),
  ``cond`` branches are compared event-for-event, and ``while`` /
  ``scan`` bodies contribute a repeated sub-sequence;
* a taint analysis seeded at ``axis_index`` tracks which values are
  rank-dependent, flowing through arithmetic, nested jaxprs, and loop
  carries — so the checker can distinguish "these branches differ and
  the predicate *provably* differs per rank" (a certain deadlock) from
  "these branches differ and the predicate might" (a hazard).

============================  =========  ====================================
rule                          severity   hazard
============================  =========  ====================================
spmd-divergent-collectives    error      cond branches issue different
                                         collective sequences (names, order,
                                         axes, or dtypes) — deadlock if the
                                         predicate differs across ranks;
                                         certain deadlock when the predicate
                                         is axis_index-tainted
spmd-rank-dependent-loop      error      a while loop that issues collectives
                                         with a rank-dependent trip count —
                                         some ranks issue more collectives
                                         than others
spmd-axis-misuse              error      a collective over a duplicated axis
                                         name, no axes at all, or an axis the
                                         caller's mesh does not define
spmd-donation-sharding        warning    a donated pjit input whose sharding
                                         matches no output — shape/dtype line
                                         up but the resharding copy defeats
                                         the donation
============================  =========  ====================================

Level 1's ``collective-divergence`` stays as the cheap structural check;
this module supersedes it with dtype-sensitivity, loop handling, and
rank-dependence proofs.  Like the rest of the package it imports without
jax — it only traverses jaxpr objects handed to it.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from .core import (ERROR, WARNING, Finding, eqn_site, filter_file_pragmas,
                   sub_closed_jaxprs)

__all__ = ["SPMD_RULES", "check_spmd", "collective_events",
           "rank_tainted_vars"]

SPMD_RULES: Dict[str, tuple] = {
    "spmd-divergent-collectives": (
        ERROR, "cond branches issue different collective sequences "
               "(order, axes, or dtypes)"),
    "spmd-rank-dependent-loop": (
        ERROR, "while loop with collectives has a rank-dependent "
               "trip count"),
    "spmd-axis-misuse": (
        ERROR, "collective over duplicate/empty/undefined axis names"),
    "spmd-donation-sharding": (
        WARNING, "donated pjit input whose sharding matches no output"),
}

_COLLECTIVE_PRIMS = {"psum", "pmax", "pmin", "ppermute", "pbroadcast",
                     "all_gather", "all_to_all", "reduce_scatter",
                     "psum_scatter", "pgather"}

# primitives that observe which rank they run on: taint sources
_RANK_PRIMS = {"axis_index"}

_LOOP_PRIMS = {"while", "scan"}


def _axes_of(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes", eqn.params.get("axis_name"))
    if axes is None:
        axes = ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _dtype_of(eqn) -> str:
    for v in eqn.invars:
        a = getattr(v, "aval", None)
        dt = getattr(a, "dtype", None)
        if dt is not None:
            return str(dt)
    return "?"


def _jaxpr_of(j):
    return getattr(j, "jaxpr", j)


# ---------------------------------------------------------------------------
# collective event sequences (the per-rank wire signature)
# ---------------------------------------------------------------------------

def collective_events(jaxpr) -> Tuple:
    """The ordered collective signature of a (Closed)Jaxpr: a tuple of
    ``(prim, axes, dtype)`` events, with cond branches folded in as a
    ``("cond", (branch_sig, ...))`` structural event and loop bodies as
    ``("loop:<prim>", body_sig)`` — two jaxprs with equal signatures
    issue, rank-for-rank, the same collectives in the same order."""
    jaxpr = _jaxpr_of(jaxpr)
    events: List[Tuple] = []
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in _COLLECTIVE_PRIMS:
            events.append((prim, _axes_of(eqn), _dtype_of(eqn)))
        elif prim == "cond":
            branches = eqn.params.get("branches") or ()
            events.append(("cond", tuple(collective_events(b)
                                         for b in branches)))
        elif prim in _LOOP_PRIMS:
            body = (eqn.params.get("body_jaxpr")
                    or eqn.params.get("jaxpr"))
            cond_j = eqn.params.get("cond_jaxpr")
            sub = ()
            if cond_j is not None:
                sub += collective_events(cond_j)
            if body is not None:
                sub += collective_events(body)
            if sub:
                events.append((f"loop:{prim}", sub))
        else:
            for sub in sub_closed_jaxprs(eqn):  # pjit/remat/custom_*: inline
                events.extend(collective_events(sub))
    return tuple(events)


def _fmt_events(events: Sequence, limit: int = 4) -> str:
    parts = []
    for ev in events[:limit]:
        if ev[0] == "cond":
            parts.append("cond(...)")
        elif ev[0].startswith("loop:"):
            parts.append(f"{ev[0]}[{_fmt_events(ev[1])}]")
        else:
            prim, axes, dtype = ev
            parts.append(f"{prim}({','.join(axes)}):{dtype}")
    if len(events) > limit:
        parts.append(f"... +{len(events) - limit}")
    return ", ".join(parts) or "none"


# ---------------------------------------------------------------------------
# rank-dependence taint (seeded at axis_index, flows through everything)
# ---------------------------------------------------------------------------

def rank_tainted_vars(jaxpr, tainted_in: Optional[Set] = None,
                      _depth: int = 0) -> Set:
    """The set of variables in ``jaxpr`` whose value can differ across
    ranks.  ``tainted_in`` marks which of the jaxpr's invars arrive
    tainted; taint propagates through every eqn (any tainted input
    taints all outputs), into and out of nested jaxprs, and around loop
    carries (bodies are re-run to a fixpoint)."""
    jaxpr = _jaxpr_of(jaxpr)
    tainted: Set = set(tainted_in or ())
    if _depth > 16:
        return tainted

    def is_tainted(v) -> bool:
        return not hasattr(v, "val") and v in tainted  # Literals never

    changed = True
    passes = 0
    while changed and passes < 8:  # fixpoint for loop-carried taint
        changed = False
        passes += 1
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim in _RANK_PRIMS:
                taint_out = True
            elif prim in _COLLECTIVE_PRIMS:
                # a full reduction over the mesh RE-synchronizes the
                # value (psum of x is rank-uniform if x's divergence is
                # what's being reduced) — but proving that needs value
                # semantics, so stay conservative: propagate input taint.
                taint_out = any(is_tainted(v) for v in eqn.invars)
            else:
                taint_out = any(is_tainted(v) for v in eqn.invars)
                subs = sub_closed_jaxprs(eqn)
                if subs and (taint_out or _has_rank_prim(subs)):
                    taint_out = _sub_taint(eqn, subs, is_tainted, _depth)
            if taint_out:
                for v in eqn.outvars:
                    if v not in tainted:
                        tainted.add(v)
                        changed = True
    return tainted


def _has_rank_prim(subs) -> bool:
    for sub in subs:
        j = _jaxpr_of(sub)
        for eqn in j.eqns:
            if eqn.primitive.name in _RANK_PRIMS:
                return True
            if _has_rank_prim(sub_closed_jaxprs(eqn)):
                return True
    return False


def _sub_taint(eqn, subs, is_tainted, depth) -> bool:
    """Whether any sub-jaxpr output of a higher-order eqn is tainted,
    mapping outer invar taint onto inner invars positionally (cond's
    leading predicate operand is dropped for branch jaxprs)."""
    for sub in subs:
        inner = _jaxpr_of(sub)
        invars = eqn.invars
        if eqn.primitive.name == "cond":
            invars = invars[1:]  # branches see the operands, not the pred
        offset = max(0, len(invars) - len(inner.invars))
        seed = set()
        for iv, ov in zip(inner.invars, invars[offset:]):
            if is_tainted(ov):
                seed.add(iv)
        inner_tainted = rank_tainted_vars(inner, seed, _depth=depth + 1)
        if any(v in inner_tainted for v in inner.outvars
               if not hasattr(v, "val")):
            return True
    return False


def _pred_is_rank_dependent(eqn, tainted: Set) -> bool:
    """cond: is the branch-index operand tainted?"""
    if not eqn.invars:
        return False
    v = eqn.invars[0]
    return not hasattr(v, "val") and v in tainted


def _while_trip_rank_dependent(eqn, tainted: Set) -> bool:
    """while: is the cond_jaxpr's predicate tainted, given carry taint
    and any axis_index inside the cond itself?"""
    cond_j = eqn.params.get("cond_jaxpr")
    if cond_j is None:
        return False
    inner = _jaxpr_of(cond_j)
    offset = max(0, len(eqn.invars) - len(inner.invars))
    seed = set()
    for iv, ov in zip(inner.invars, eqn.invars[offset:]):
        if not hasattr(ov, "val") and ov in tainted:
            seed.add(iv)
    inner_tainted = rank_tainted_vars(inner, seed)
    return any(v in inner_tainted for v in inner.outvars
               if not hasattr(v, "val"))


# ---------------------------------------------------------------------------
# the checks
# ---------------------------------------------------------------------------

def _finding(rule: str, msg: str, eqn=None, name=None, **extra) -> Finding:
    severity, _ = SPMD_RULES[rule]
    file, line, where = eqn_site(eqn) if eqn is not None else (None, None,
                                                              "<jaxpr>")
    extra.setdefault("where", where)
    return Finding(rule=rule, severity=severity, message=msg, file=file,
                   line=line, function=name, source="spmd", extra=extra)


def _walk(jaxpr, visit, _depth=0):
    """Call ``visit(eqn, jaxpr)`` for every eqn, recursing into every
    nested jaxpr (branches, bodies, pjit — the interprocedural walk)."""
    jaxpr = _jaxpr_of(jaxpr)
    if _depth > 32:
        return
    for eqn in jaxpr.eqns:
        visit(eqn, jaxpr)
        for sub in sub_closed_jaxprs(eqn):
            _walk(sub, visit, _depth + 1)


def _check_divergence(closed, name, want_cond: bool, want_loop: bool,
                      out: List[Finding]):
    """The taint-aware walk: recompute the tainted-var set for every
    nested jaxpr (seeding inner invars from outer taint), so a cond
    buried inside jit's pjit wrapper still sees its predicate's
    rank-dependence."""

    def recurse(jaxpr, tainted_in: Set, depth: int):
        jaxpr = _jaxpr_of(jaxpr)
        if depth > 16:
            return
        tainted = rank_tainted_vars(jaxpr, tainted_in)
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "cond" and want_cond:
                _report_divergent_cond(eqn, tainted, name, out)
            if prim == "while" and want_loop:
                _report_rank_dependent_loop(eqn, tainted, name, out)
            invars = eqn.invars[1:] if prim == "cond" else eqn.invars
            for sub in sub_closed_jaxprs(eqn):
                inner = _jaxpr_of(sub)
                offset = max(0, len(invars) - len(inner.invars))
                seed = {iv for iv, ov in zip(inner.invars, invars[offset:])
                        if not hasattr(ov, "val") and ov in tainted}
                recurse(inner, seed, depth + 1)

    recurse(closed, set(), 0)


def _report_divergent_cond(eqn, tainted, name, out: List[Finding]):
    branches = eqn.params.get("branches") or ()
    sigs = [collective_events(b) for b in branches]
    if len(set(sigs)) <= 1:
        return
    rank_dep = _pred_is_rank_dependent(eqn, tainted)
    desc = "; ".join(f"branch {i}: {_fmt_events(s)}"
                     for i, s in enumerate(sigs))
    certainty = ("the predicate is derived from axis_index, so ranks "
                 "WILL take different branches — this deadlocks"
                 if rank_dep else
                 "if the predicate differs across ranks this deadlocks")
    out.append(_finding(
        "spmd-divergent-collectives",
        f"cond branches issue different collective sequences "
        f"({desc}); {certainty} the mesh at the first mismatched "
        "collective — make every branch issue the identical "
        "sequence (same order, axes, and dtypes) or hoist the "
        "collectives out of the cond",
        eqn=eqn, name=name, rank_dependent=rank_dep, branches=desc))


def _report_rank_dependent_loop(eqn, tainted, name, out: List[Finding]):
    body = eqn.params.get("body_jaxpr")
    body_events = collective_events(body) if body is not None else ()
    if not body_events:
        return
    if _while_trip_rank_dependent(eqn, tainted):
        out.append(_finding(
            "spmd-rank-dependent-loop",
            f"while loop issues collectives ({_fmt_events(body_events)}) "
            "but its trip count depends on axis_index — ranks exit "
            "after different iteration counts and the extra "
            "iterations' collectives block forever; make the trip "
            "count rank-uniform (e.g. psum/pmax the continue flag) "
            "or move the collectives out of the loop",
            eqn=eqn, name=name))


def _check_axis_misuse(closed, axis_names, name, out: List[Finding]):
    known = set(axis_names) if axis_names is not None else None

    def visit(eqn, owner):
        if eqn.primitive.name not in _COLLECTIVE_PRIMS:
            return
        axes = _axes_of(eqn)
        if len(axes) != len(set(axes)):
            out.append(_finding(
                "spmd-axis-misuse",
                f"{eqn.primitive.name} lists axis "
                f"{[a for a in axes if axes.count(a) > 1][0]!r} more than "
                f"once ({list(axes)}) — a duplicated mesh axis reduces "
                "twice over the same ranks",
                eqn=eqn, name=name, axes=list(axes)))
        elif not axes:
            out.append(_finding(
                "spmd-axis-misuse",
                f"{eqn.primitive.name} names no axes — the collective "
                "is a no-op on every mesh; name the mesh axis to reduce "
                "over",
                eqn=eqn, name=name, axes=[]))
        elif known is not None:
            unknown = [a for a in axes if a not in known]
            if unknown:
                out.append(_finding(
                    "spmd-axis-misuse",
                    f"{eqn.primitive.name} reduces over axis "
                    f"{unknown[0]!r} but the mesh only defines "
                    f"{sorted(known)} — this fails (or worse, silently "
                    "rebinds) the moment the program runs on the real "
                    "mesh",
                    eqn=eqn, name=name, axes=list(axes),
                    known=sorted(known)))
    _walk(closed, visit)


def _sharding_repr(s) -> Optional[str]:
    if s is None or type(s).__name__ in ("UnspecifiedValue",):
        return None
    try:
        return repr(s)
    except Exception:  # exotic sharding object — treat as unconstrained
        return None


def _check_donation_sharding(closed, name, out: List[Finding]):
    def visit(eqn, owner):
        donated = eqn.params.get("donated_invars")
        in_sh = eqn.params.get("in_shardings")
        out_sh = eqn.params.get("out_shardings")
        if not donated or not any(donated) or in_sh is None \
                or out_sh is None:
            return
        out_slots = []
        for v, sh in zip(eqn.outvars, out_sh):
            a = getattr(v, "aval", None)
            out_slots.append((tuple(getattr(a, "shape", ())),
                              str(getattr(a, "dtype", "")),
                              _sharding_repr(sh)))
        for i, (v, don, sh) in enumerate(zip(eqn.invars, donated, in_sh)):
            if not don:
                continue
            a = getattr(v, "aval", None)
            sig = (tuple(getattr(a, "shape", ())),
                   str(getattr(a, "dtype", "")))
            srep = _sharding_repr(sh)
            if srep is None:
                continue  # unconstrained input sharding can alias anything
            matches = [o for o in out_slots if o[:2] == sig]
            if not matches:
                continue  # no shape/dtype match at all: Level 1's rule
            usable = [o for o in matches if o[2] is None or o[2] == srep]
            if usable:
                out_slots.remove(usable[0])
                continue
            out.append(_finding(
                "spmd-donation-sharding",
                f"donated argument {i} ({sig[1]}{list(sig[0])}) matches "
                "an output by shape/dtype but not by sharding — XLA "
                "inserts a resharding copy and the donated buffer "
                "cannot be reused; align in_shardings/out_shardings or "
                "drop the donation",
                eqn=eqn, name=name, arg_index=i))
    _walk(closed, visit)


# ---------------------------------------------------------------------------
# entry point (merged into jaxpr_checks.check_jaxpr)
# ---------------------------------------------------------------------------

def check_spmd(closed, name: Optional[str] = None,
               axis_names: Optional[Sequence[str]] = None,
               config: Optional[dict] = None, rules=None) -> List[Finding]:
    """Run the SPMD consistency rules over a ClosedJaxpr.
    ``axis_names``, when given, is the set of mesh axes the deployment
    actually defines (enables the undefined-axis check)."""
    out: List[Finding] = []
    want = lambda r: rules is None or r in rules
    want_cond = want("spmd-divergent-collectives")
    want_loop = want("spmd-rank-dependent-loop")
    if want_cond or want_loop:
        _check_divergence(closed, name, want_cond, want_loop, out)
    if want("spmd-axis-misuse"):
        _check_axis_misuse(closed, axis_names, name, out)
    if want("spmd-donation-sharding"):
        _check_donation_sharding(closed, name, out)
    return filter_file_pragmas(out)
