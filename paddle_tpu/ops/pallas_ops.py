"""Pallas TPU kernels for the hot ops.

Reference analog: paddle/fluid/operators/fused/ (fused_attention_op.cu,
fmha_ref.h) and phi/kernels/fusion — the hand-written CUDA fused kernels.
On TPU the equivalents are Pallas kernels; each has a jnp fallback (used on
CPU meshes, in tests, and whenever shapes don't meet the MXU tiling
constraints), so the op surface is identical everywhere.

Flash (causal) attention: forward with online softmax emitting the
per-row logsumexp, and a true flash backward (dq kernel + dk/dv kernel)
that recomputes attention probabilities block-wise from the saved LSE —
no O(S^2) materialization in either direction.

Two kernel variants, auto-selected by sequence length (_use_resident):

- "resident" (short S): the non-grid sequence operands (k/v for fwd/dq,
  q/g/o/lse for dkv) live whole in VMEM and an in-kernel fori_loop walks
  them, skipping fully-masked causal blocks outright. Fastest, but VMEM
  residency grows with S — stops compiling around S=8192 on 16MB parts.
- "streamed" (long S): BOTH sequence dimensions ride grid axes — grid
  (BH, S/bq, S/bk) with the contraction axis innermost — carrying
  running statistics (m/l/acc for the forward's online softmax; dq/dk/dv
  partials for the backwards) in VMEM scratch initialized when the
  innermost index is 0 and flushed to the revisited output block on the
  last step. VMEM is a function of BLOCK sizes only: S=8k/32k compile
  with the same footprint as S=2k. Masked causal blocks are predicated
  out (@pl.when) rather than skipped, which is the price of the
  streaming (~30% at S=2k — why the resident variant is kept).

TPU layout notes (Mosaic tiling):
- Every HBM<->VMEM block must have its last dim divisible by 128 (or equal
  to the array dim) and its second-to-last divisible by 8 (or equal) —
  see ``mosaic_block_legal`` below, which mirrors the rule in
  jax/_src/pallas/mosaic/lowering.py::_check_block_mappings and is unit
  tested against every BlockSpec this module creates.
- Per-row statistics (LSE) travel as [.., S, 128] tiles with the scalar
  replicated across the 128 lanes — never as a bare [.., S] vector,
  whose (1, bq) block is Mosaic-illegal.

Set ``_INTERPRET = True`` (tests do) to run the kernels through the Pallas
interpreter on CPU for numerical validation without TPU hardware.
"""
from __future__ import annotations

import functools
import json
import logging
import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["causal_attention", "flash_attention_available",
           "mosaic_block_legal", "flash_block_specs",
           "tune_causal_attention", "flash_candidates",
           "fused_attention_block", "fused_mlp_block",
           "fused_attention_available", "fused_mlp_available",
           "fused_attn_block_specs", "fused_mlp_block_specs",
           "fused_attn_candidates", "fused_mlp_candidates",
           "tune_fused_blocks", "fused_parity_cases",
           "ragged_paged_attention", "ragged_attention_available",
           "paged_kv_write", "kv_write_available",
           "selective_scan", "ssm_scan_available", "scan_positions",
           "int8_matmul", "int8_matmul_available",
           "int8_matmul_block_specs", "int8_matmul_candidates",
           "tune_int8_matmul", "quantize_int8"]

_BQ = 256
_BK = 256
_LANES = 128  # TPU lane width; row stats are replicated across it

# Block-size axis values the candidate generators draw from. Every value
# is a multiple of both the 128-lane tile and the 8-sublane tile, so the
# raw pool can only produce Mosaic-aligned dims; the generators then
# validate every derived BlockSpec with mosaic_block_legal before a
# candidate becomes visible (illegal shapes are unrepresentable — the
# BENCH_r02 (1, 256) failure class cannot be emitted).
_POW2_BLOCKS = (128, 256, 512, 1024)

# Legacy static (bq, bk) pool, kept as the seed ordering for
# flash_candidates (preference order: measured-good defaults first).
_BLOCK_CANDIDATES = ((256, 256), (512, 512), (512, 256), (256, 512),
                     (128, 256), (256, 128), (1024, 512), (512, 1024),
                     (128, 128), (1024, 1024))

# VMEM working-set ceiling for candidate generation (16MB parts, minus
# headroom for Mosaic's own spills). Candidates whose resident blocks +
# scratch exceed it are disqualified up front instead of failing at
# compile time inside the tuning loop.
_VMEM_BUDGET = 12 * 2 ** 20

# Flip to True to force the Pallas path through the interpreter (CPU tests).
_INTERPRET = False


def _on_tpu():
    return jax.default_backend() == "tpu"


def kernel_axes():
    """Where a Pallas kernel may run at this point of the trace.

    GSPMD cannot partition a Mosaic custom call, and JAX 0.9.0 lowers one
    only when the program is unpartitioned or the call sits in ONE
    shard_map that is manual over every mesh axis ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a shard_map",
    jax/_src/tpu_custom_call.py: manual_axes != mesh.axis_names — nested
    partial regions do not add up). The mesh comes from the trace context
    (``jax.set_mesh`` around the train step, or an enclosing shard_map).

    Returns ``()`` — call the kernel directly (no mesh, one device, or
    already inside a fully manual region); the tuple of all mesh axes —
    the caller wraps the call in a shard_map over them; or None — no
    kernel here: the trace is inside a partially manual region (the
    pipeline's shard_map over 'pp', the overlap step's over 'dp'), where
    every ``*_available()`` below answers False, says so once by kernel
    family (_kernels_enabled), and the jnp/XLA body serves."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty or am.size == 1 or \
            set(am.manual_axes) == set(am.axis_names):
        return ()
    if am.manual_axes:
        return None
    return tuple(am.axis_names)


_LOG = logging.getLogger(__name__)
_ANNOUNCED = set()   # kernel families _kernels_enabled has logged as dropped


def _kernels_enabled(kernel):
    """A TPU backend (or the interpreter), and a place in the trace where
    a Mosaic call can be lowered (kernel_axes). A kernel family dropped
    for its place in the trace is logged once, by name."""
    if not (_on_tpu() or _INTERPRET):
        return False
    if kernel_axes() is not None:
        return True
    if kernel not in _ANNOUNCED:
        _ANNOUNCED.add(kernel)
        am = jax.sharding.get_abstract_mesh()
        _LOG.warning(
            "%s: no Pallas kernel inside a shard_map that is manual over "
            "%s of the mesh axes %s (a Mosaic call needs every axis "
            "manual); its jnp/XLA body runs there", kernel,
            sorted(am.manual_axes), list(am.axis_names))
    return False


def _blocks_legal(bq, bk, S, D):
    """A cached/tuned (bq, bk) is usable iff it tiles S and every derived
    HBM BlockSpec is Mosaic-legal, plus the kernel-internal constraint
    that bk feeds _rep_lanes (bk % 128). Guards against hand-edited or
    stale persisted autotune caches breaking compilation."""
    if S % bq or S % bk or S < bq or bk % _LANES:
        return False
    specs = flash_block_specs(8, S, D, bq, bk)
    return all(mosaic_block_legal(blk, arr)
               for groups in specs.values()
               for io in ("in", "out")
               for blk, arr in groups[io])


def _flash_keys(S, D, dtype=None):
    """Cache-key chain for the flash (bq, bk) entry, most-specific first:
    the full context key (dtype + device kind + jaxlib version — a
    v5e-tuned cache never mis-seeds another topology or toolchain), then
    the legacy dtype-only key (committed caches), then the legacy
    any-dtype key (pre-dtype caches)."""
    from paddle_tpu.ops import autotune
    keys = []
    if dtype is not None:
        dstr = str(jnp.dtype(dtype))
        keys.append(["blocks", int(S), int(D)] + autotune.context_key(dstr))
        keys.append(["blocks", int(S), int(D), dstr])
    keys.append(["blocks", int(S), int(D)])
    return keys


def _block_config(S, D, dtype=None):
    """Active (bq, bk) for a given sequence/head-dim/dtype: the autotuned
    winner if one is cached (see tune_causal_attention), else the 256x256
    default. Read at trace time, so jitted graphs bake in the choice."""
    from paddle_tpu.ops import autotune
    cfg = autotune.lookup_chain("flash_attention", _flash_keys(S, D, dtype))
    if cfg is not None and _blocks_legal(int(cfg[0]), int(cfg[1]), S, D):
        return int(cfg[0]), int(cfg[1])
    return _BQ, _BK


def flash_candidates(S, D, dtype=jnp.float32):
    """Legal-by-construction (bq, bk) candidates for the flash kernels at
    this shape: the static preference pool plus the power-of-two grid,
    filtered through autotune.legal_candidates so every derived BlockSpec
    passes mosaic_block_legal (and tiles S). The tuner can only ever
    measure configs that compile."""
    from paddle_tpu.ops import autotune
    pool = list(_BLOCK_CANDIDATES) + [
        (bq, bk) for bq in _POW2_BLOCKS for bk in _POW2_BLOCKS
        if (bq, bk) not in _BLOCK_CANDIDATES]

    def spec_fn(cand):
        bq, bk = cand
        if S % bq or S % bk or S < bq or bk % _LANES:
            return None
        specs = flash_block_specs(8, S, D, bq, bk)
        return [pair for groups in specs.values()
                for io in ("in", "out") for pair in groups[io]]

    bits = 8 * jnp.dtype(dtype).itemsize
    return autotune.legal_candidates(pool, spec_fn, dtype_bits=bits)


def flash_attention_available(q_shape, dtype=None):
    B, S, H, D = q_shape
    bq, bk = _block_config(S, D, dtype)
    shapes_ok = D % 128 == 0 and S % bq == 0 and S % bk == 0 and S >= bq
    return shapes_ok and _kernels_enabled("flash_attention")


def mosaic_block_legal(block_shape, array_shape, dtype_bits=32):
    """Pure-shape mirror of Mosaic's _check_block_mappings rule.

    rank >= 2: last block dim divisible by 128 or equal to the array dim,
    second-to-last divisible by 8 or equal. rank 1: divisible by
    128 * (32 // dtype_bits) or equal.
    """
    bs = tuple(int(d) for d in block_shape)
    ashape = tuple(int(d) for d in array_shape)
    if len(bs) != len(ashape) or len(bs) < 1:
        return False
    if len(bs) >= 2:
        ok_last = bs[-1] == ashape[-1] or bs[-1] % 128 == 0
        ok_sub = bs[-2] == ashape[-2] or bs[-2] % 8 == 0
        return ok_last and ok_sub
    tiling = 128 * (32 // dtype_bits)
    return bs[0] == ashape[0] or bs[0] % tiling == 0


# Above this many bytes of whole-sequence VMEM residency (the bwd_dkv
# kernel's q/g/o [S, D] + lse [S, 128] f32 working set), the loop-based
# "resident" kernels stop compiling on 16MB-VMEM parts; the streamed
# variant (grid-blocked everything + scratch accumulators) takes over.
# Resident is ~30% faster at short S (its in-kernel loop skips masked
# causal blocks entirely; the streamed grid only predicates them out).
_RESIDENT_MAX_BYTES = 6 * 2 ** 20


def _use_resident(S, D, itemsize=2):
    return 3 * S * D * itemsize + S * _LANES * 4 <= _RESIDENT_MAX_BYTES


def flash_block_specs(BH, S, D, bq=_BQ, bk=_BK, resident=None):
    """(block_shape, array_shape) for every HBM operand of the three flash
    kernels — the single source the pallas_calls below and the shape unit
    test both consume. Two variants (auto-selected by S): "resident"
    keeps k/v (fwd, dq) and q/g/o/lse (dkv) whole in VMEM and loops
    in-kernel; "streamed" blocks every operand on the grid."""
    if resident is None:
        resident = _use_resident(S, D)
    qblk = ((1, bq, D), (BH, S, D))
    kblk = ((1, bk, D), (BH, S, D))
    lse_q = ((1, bq, _LANES), (BH, S, _LANES))
    if not resident:
        return {
            "fwd": {"in": [qblk, kblk, kblk], "out": [qblk, lse_q]},
            "bwd_dq": {"in": [qblk, kblk, kblk, qblk, qblk, lse_q],
                       "out": [qblk]},
            "bwd_dkv": {"in": [qblk, kblk, kblk, qblk, qblk, lse_q],
                        "out": [kblk, kblk]},
        }
    full = ((1, S, D), (BH, S, D))
    lse_full = ((1, S, _LANES), (BH, S, _LANES))
    return {
        "fwd": {"in": [qblk, full, full], "out": [qblk, lse_q]},
        "bwd_dq": {"in": [qblk, full, full, qblk, qblk, lse_q],
                   "out": [qblk]},
        "bwd_dkv": {"in": [full, kblk, kblk, full, full, lse_full],
                    "out": [kblk, kblk]},
    }


# ---------------------------------------------------------------------------
# jnp fallback (XLA-fused)
# ---------------------------------------------------------------------------

def _attention_jnp(q, k, v):
    scale = 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)  # [B,H,S,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhsd,bhtd->bhst", qt, kt) * scale
    S = logits.shape[-1]
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


def _rep_lanes(col, n_lanes):
    """[R, 1] -> [R, n_lanes] via the broadcast-to-128-then-tile idiom that
    Mosaic is known to lower (jax's reference flash kernel does the same)."""
    t = jnp.broadcast_to(col, (col.shape[0], _LANES))
    reps = n_lanes // _LANES
    return t if reps == 1 else jnp.tile(t, (1, reps))


def _compiler_params(*dimension_semantics):
    from jax.experimental.pallas import tpu as pltpu
    if not dimension_semantics:
        dimension_semantics = ("parallel", "parallel", "arbitrary")
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics))


def _pallas_call(kernel, own_dma=False, **kwargs):
    """The one door to ``pl.pallas_call``: the call is made under
    ``jax.named_scope("pallas/<kernel function name>")``, so every Mosaic
    custom call carries its kernel's name in its ``op_name`` on the device
    trace — the name the lowered text's ``kernel_name`` already has.
    ``interpret`` follows ``_INTERPRET``.  A kernel that issues its own
    copies and waits on their semaphores (``own_dma``) is interpreted by
    the TPU interpreter, which models both: a copy lands when it is waited
    for and scratch starts as NaN, where the plain interpreter copies at
    once, waits for nothing and starts from zeros."""
    from jax.experimental import pallas as pl
    scope = f"pallas/{getattr(kernel, 'func', kernel).__name__}"
    interpret = _INTERPRET
    if interpret and own_dma:
        from jax.experimental.pallas import tpu as pltpu
        interpret = pltpu.InterpretParams()
    call = pl.pallas_call(kernel, interpret=interpret, **kwargs)

    def scoped(*operands):
        with jax.named_scope(scope):
            return call(*operands)
    return scoped


# ---------------------------------------------------------------------------
# Pallas flash forward (emits LSE for the backward)
# ---------------------------------------------------------------------------

def _flash_fwd_kernel_streamed(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_s, l_s, acc_s, *, bq, bk, scale):
    from jax.experimental import pallas as pl
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kb = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # causal: the block contributes iff its first key position is within
    # this q block's band
    @pl.when(ki * bk < (qi + 1) * bq)
    def _update():
        q = q_ref[0].astype(jnp.float32)           # [bq, D]
        D = q.shape[-1]
        k = k_ref[0].astype(jnp.float32)           # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        m = m_s[...]
        l = l_s[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1)[:, None])  # [bq, 128]
        p = jnp.exp(s - _rep_lanes(m_new[:, :1], bk))
        corr = jnp.exp(m - m_new)
        l_s[...] = l * corr + jnp.sum(p, axis=-1)[:, None]
        m_s[...] = m_new
        acc_s[...] = acc_s[...] * _rep_lanes(corr[:, :1], D) + lax.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(ki == n_kb - 1)
    def _flush():
        D = acc_s.shape[-1]
        l = l_s[...]
        o_ref[0] = (acc_s[...] / _rep_lanes(l[:, :1], D)).astype(
            o_ref.dtype)
        lse_ref[0] = m_s[...] + jnp.log(l)


def _flash_fwd_streamed(q, k, v, bq=None, bk=None):
    """q,k,v: [BH, S, D] → (out [BH,S,D], lse [BH,S,128] fp32, value
    replicated across the trailing lane dim)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    BH, S, D = q.shape
    if bq is None or bk is None:
        bq, bk = _block_config(S, D, q.dtype)
    scale = 1.0 / math.sqrt(D)
    specs = flash_block_specs(BH, S, D, bq, bk, resident=False)["fwd"]
    grid = (BH, S // bq, S // bk)
    by_q = lambda b, i, j: (b, i, 0)  # noqa: E731
    by_k = lambda b, i, j: (b, j, 0)  # noqa: E731
    out, lse = _pallas_call(
        functools.partial(_flash_fwd_kernel_streamed, bq=bq, bk=bk, scale=scale),
        out_shape=(jax.ShapeDtypeStruct((BH, S, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, S, _LANES), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec(specs["in"][0][0], by_q),
            pl.BlockSpec(specs["in"][1][0], by_k),
            pl.BlockSpec(specs["in"][2][0], by_k),
        ],
        out_specs=(pl.BlockSpec(specs["out"][0][0], by_q),
                   pl.BlockSpec(specs["out"][1][0], by_q)),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running max
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running sum
            pltpu.VMEM((bq, D), jnp.float32),        # output accumulator
        ],
        compiler_params=_compiler_params(),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Pallas flash backward: dq kernel (streams k blocks on the grid)
# ---------------------------------------------------------------------------

def _flash_bwd_dq_kernel_streamed(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
                         dq_ref, dq_s, *, bq, bk, scale):
    from jax.experimental import pallas as pl
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kb = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    @pl.when(ki * bk < (qi + 1) * bq)
    def _update():
        q = q_ref[0].astype(jnp.float32)            # [bq, D]
        g = g_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                            # [bq, 128]
        delta = jnp.sum(g * o, axis=-1)[:, None]    # [bq, 1]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        p = jnp.where(q_pos >= k_pos,
                      jnp.exp(s - _rep_lanes(lse[:, :1], bk)), 0.0)
        dp = lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - _rep_lanes(delta, bk))
        dq_s[...] = dq_s[...] + lax.dot(
            ds, k, preferred_element_type=jnp.float32)

    @pl.when(ki == n_kb - 1)
    def _flush():
        dq_ref[0] = (dq_s[...] * scale).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# Pallas flash backward: dk/dv kernel (streams q blocks on the grid)
# ---------------------------------------------------------------------------

def _flash_bwd_dkv_kernel_streamed(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
                          dk_ref, dv_ref, dk_s, dv_s, *, bq, bk, scale):
    from jax.experimental import pallas as pl
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    n_qb = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    # causal: q blocks strictly before this k block are fully masked
    @pl.when((qi + 1) * bq > ki * bk)
    def _update():
        k = k_ref[0].astype(jnp.float32)            # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)            # [bq, D]
        g = g_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                            # [bq, 128]
        delta = jnp.sum(g * o, axis=-1)[:, None]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        p = jnp.where(q_pos >= k_pos,
                      jnp.exp(s - _rep_lanes(lse[:, :1], bk)), 0.0)
        dv_s[...] = dv_s[...] + lax.dot_general(
            p, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - _rep_lanes(delta, bk))
        dk_s[...] = dk_s[...] + lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == n_qb - 1)
    def _flush():
        dk_ref[0] = (dk_s[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _flash_bwd_streamed(q, k, v, g, o, lse, bq=None, bk=None):
    """q,k,v,g,o: [BH, S, D]; lse: [BH, S, 128]; returns dq, dk, dv."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    BH, S, D = q.shape
    if bq is None or bk is None:
        bq, bk = _block_config(S, D, q.dtype)
    scale = 1.0 / math.sqrt(D)
    specs = flash_block_specs(BH, S, D, bq, bk, resident=False)

    by_q = lambda b, i, j: (b, i, 0)    # noqa: E731
    by_k = lambda b, i, j: (b, j, 0)    # noqa: E731

    dq_specs = specs["bwd_dq"]
    dq = _pallas_call(
        functools.partial(_flash_bwd_dq_kernel_streamed, bq=bq, bk=bk, scale=scale),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        grid=(BH, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec(dq_specs["in"][0][0], by_q),   # q
            pl.BlockSpec(dq_specs["in"][1][0], by_k),   # k
            pl.BlockSpec(dq_specs["in"][2][0], by_k),   # v
            pl.BlockSpec(dq_specs["in"][3][0], by_q),   # g
            pl.BlockSpec(dq_specs["in"][4][0], by_q),   # o
            pl.BlockSpec(dq_specs["in"][5][0], by_q),   # lse
        ],
        out_specs=pl.BlockSpec(dq_specs["out"][0][0], by_q),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_compiler_params(),
    )(q, k, v, g, o, lse)

    # dkv grid: k blocks ride dim 1 (the by_q map), q blocks stream on
    # dim 2 (the by_k map) — same two index maps, roles swapped
    by_kv, by_qs = by_q, by_k
    dkv_specs = specs["bwd_dkv"]
    dk, dv = _pallas_call(
        functools.partial(_flash_bwd_dkv_kernel_streamed, bq=bq, bk=bk,
                          scale=scale),
        out_shape=(jax.ShapeDtypeStruct((BH, S, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, S, D), v.dtype)),
        grid=(BH, S // bk, S // bq),
        in_specs=[
            pl.BlockSpec(dkv_specs["in"][0][0], by_qs),   # q
            pl.BlockSpec(dkv_specs["in"][1][0], by_kv),   # k
            pl.BlockSpec(dkv_specs["in"][2][0], by_kv),   # v
            pl.BlockSpec(dkv_specs["in"][3][0], by_qs),   # g
            pl.BlockSpec(dkv_specs["in"][4][0], by_qs),   # o
            pl.BlockSpec(dkv_specs["in"][5][0], by_qs),   # lse
        ],
        out_specs=(pl.BlockSpec(dkv_specs["out"][0][0], by_kv),
                   pl.BlockSpec(dkv_specs["out"][1][0], by_kv)),
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_compiler_params(),
    )(q, k, v, g, o, lse)
    return dq, dk, dv


def _flash_fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, bq, bk, scale):
    from jax.experimental import pallas as pl
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)          # [bq, D]
    D = q.shape[-1]
    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    n_kblocks = (qi * bq + bq + bk - 1) // bk  # causal: skip fully-masked

    def body(i, carry):
        m, l, acc = carry                      # m, l: [bq, 128]
        k = k_ref[0, pl.ds(i * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * bk, bk), :].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        k_pos = i * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1)[:, None])   # [bq, 128]
        p = jnp.exp(s - _rep_lanes(m_new[:, :1], bk))
        corr = jnp.exp(m - m_new)                              # [bq, 128]
        l_new = l * corr + jnp.sum(p, axis=-1)[:, None]
        acc_new = acc * _rep_lanes(corr[:, :1], D) + lax.dot(
            p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, _LANES), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq, _LANES), jnp.float32)
    acc0 = jnp.zeros((bq, D), jnp.float32)
    m, l, acc = lax.fori_loop(0, n_kblocks, body, (m0, l0, acc0))
    o_ref[0] = (acc / _rep_lanes(l[:, :1], D)).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)                                # [bq, 128]


def _flash_fwd_resident(q, k, v, bq=None, bk=None):
    """q,k,v: [BH, S, D] → (out [BH,S,D], lse [BH,S,128] fp32, value
    replicated across the trailing lane dim)."""
    from jax.experimental import pallas as pl
    BH, S, D = q.shape
    if bq is None or bk is None:
        bq, bk = _block_config(S, D, q.dtype)
    scale = 1.0 / math.sqrt(D)
    specs = flash_block_specs(BH, S, D, bq, bk, resident=True)["fwd"]
    grid = (BH, S // bq)
    blocked = lambda b, i: (b, i, 0)  # noqa: E731
    whole = lambda b, i: (b, 0, 0)    # noqa: E731
    out, lse = _pallas_call(
        functools.partial(_flash_fwd_kernel_resident, bq=bq, bk=bk, scale=scale),
        out_shape=(jax.ShapeDtypeStruct((BH, S, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, S, _LANES), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec(specs["in"][0][0], blocked),
            pl.BlockSpec(specs["in"][1][0], whole),
            pl.BlockSpec(specs["in"][2][0], whole),
        ],
        out_specs=(pl.BlockSpec(specs["out"][0][0], blocked),
                   pl.BlockSpec(specs["out"][1][0], blocked)),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Pallas flash backward: dq kernel (loops over k blocks)
# ---------------------------------------------------------------------------

def _flash_bwd_dq_kernel_resident(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
                         dq_ref, *, bq, bk, scale):
    from jax.experimental import pallas as pl
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)            # [bq, D]
    g = g_ref[0].astype(jnp.float32)            # [bq, D]
    o = o_ref[0].astype(jnp.float32)            # [bq, D]
    lse = lse_ref[0]                            # [bq, 128]
    delta = jnp.sum(g * o, axis=-1)[:, None]    # [bq, 1]
    D = q.shape[-1]
    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    lse_bk = _rep_lanes(lse[:, :1], bk)         # [bq, bk]
    delta_bk = _rep_lanes(delta, bk)            # [bq, bk]

    n_kblocks = (qi * bq + bq + bk - 1) // bk

    def body(i, dq):
        k = k_ref[0, pl.ds(i * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * bk, bk), :].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        k_pos = i * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        p = jnp.where(q_pos >= k_pos, jnp.exp(s - lse_bk), 0.0)
        dp = lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_bk)
        return dq + lax.dot(ds, k, preferred_element_type=jnp.float32)

    dq = lax.fori_loop(0, n_kblocks, body, jnp.zeros((bq, D), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# Pallas flash backward: dk/dv kernel (loops over q blocks)
# ---------------------------------------------------------------------------

def _flash_bwd_dkv_kernel_resident(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
                          dk_ref, dv_ref, *, bq, bk, scale, n_qblocks):
    from jax.experimental import pallas as pl
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)            # [bk, D]
    v = v_ref[0].astype(jnp.float32)            # [bk, D]
    D = k.shape[-1]
    k_pos = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    first_q = (ki * bk) // bq  # causal: earlier q blocks are fully masked

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * bq, bq), :].astype(jnp.float32)
        g = g_ref[0, pl.ds(i * bq, bq), :].astype(jnp.float32)
        o = o_ref[0, pl.ds(i * bq, bq), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * bq, bq), :]  # [bq, 128]
        delta = jnp.sum(g * o, axis=-1)[:, None]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        q_pos = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        p = jnp.where(q_pos >= k_pos,
                      jnp.exp(s - _rep_lanes(lse[:, :1], bk)), 0.0)
        dv_new = dv + lax.dot_general(p, g, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - _rep_lanes(delta, bk))
        dk_new = dk + lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk0 = jnp.zeros((bk, D), jnp.float32)
    dv0 = jnp.zeros((bk, D), jnp.float32)
    dk, dv = lax.fori_loop(first_q, n_qblocks, body, (dk0, dv0))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_resident(q, k, v, g, o, lse, bq=None, bk=None):
    """q,k,v,g,o: [BH, S, D]; lse: [BH, S, 128]; returns dq, dk, dv."""
    from jax.experimental import pallas as pl
    BH, S, D = q.shape
    if bq is None or bk is None:
        bq, bk = _block_config(S, D, q.dtype)
    scale = 1.0 / math.sqrt(D)
    specs = flash_block_specs(BH, S, D, bq, bk, resident=True)

    blocked = lambda b, i: (b, i, 0)  # noqa: E731
    whole = lambda b, i: (b, 0, 0)    # noqa: E731

    dq_specs = specs["bwd_dq"]
    dq = _pallas_call(
        functools.partial(_flash_bwd_dq_kernel_resident, bq=bq, bk=bk, scale=scale),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        grid=(BH, S // bq),
        in_specs=[
            pl.BlockSpec(dq_specs["in"][0][0], blocked),   # q
            pl.BlockSpec(dq_specs["in"][1][0], whole),     # k
            pl.BlockSpec(dq_specs["in"][2][0], whole),     # v
            pl.BlockSpec(dq_specs["in"][3][0], blocked),   # g
            pl.BlockSpec(dq_specs["in"][4][0], blocked),   # o
            pl.BlockSpec(dq_specs["in"][5][0], blocked),   # lse
        ],
        out_specs=pl.BlockSpec(dq_specs["out"][0][0], blocked),
    )(q, k, v, g, o, lse)

    dkv_specs = specs["bwd_dkv"]
    dk, dv = _pallas_call(
        functools.partial(_flash_bwd_dkv_kernel_resident, bq=bq, bk=bk, scale=scale,
                          n_qblocks=S // bq),
        out_shape=(jax.ShapeDtypeStruct((BH, S, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, S, D), v.dtype)),
        grid=(BH, S // bk),
        in_specs=[
            pl.BlockSpec(dkv_specs["in"][0][0], whole),    # q
            pl.BlockSpec(dkv_specs["in"][1][0], blocked),  # k
            pl.BlockSpec(dkv_specs["in"][2][0], blocked),  # v
            pl.BlockSpec(dkv_specs["in"][3][0], whole),    # g
            pl.BlockSpec(dkv_specs["in"][4][0], whole),    # o
            pl.BlockSpec(dkv_specs["in"][5][0], whole),    # lse
        ],
        out_specs=(pl.BlockSpec(dkv_specs["out"][0][0], blocked),
                   pl.BlockSpec(dkv_specs["out"][1][0], blocked)),
    )(q, k, v, g, o, lse)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# variant dispatch
# ---------------------------------------------------------------------------

def _flash_fwd(q, k, v, bq=None, bk=None):
    BH, S, D = q.shape
    if _use_resident(S, D, jnp.dtype(q.dtype).itemsize):
        return _flash_fwd_resident(q, k, v, bq, bk)
    return _flash_fwd_streamed(q, k, v, bq, bk)


def _flash_bwd(q, k, v, g, o, lse, bq=None, bk=None):
    BH, S, D = q.shape
    if _use_resident(S, D, jnp.dtype(q.dtype).itemsize):
        return _flash_bwd_resident(q, k, v, g, o, lse, bq, bk)
    return _flash_bwd_streamed(q, k, v, g, o, lse, bq, bk)


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------

def _to_bh(x):
    B, S, H, D = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(B * H, S, D)


def _from_bh(x, B, H):
    BH, S, D = x.shape
    return jnp.swapaxes(x.reshape(B, H, S, D), 1, 2)


@jax.custom_vjp
def causal_attention(q, k, v):
    """Causal self-attention, [B, S, H, D] layout. Pallas flash kernel on
    TPU for qualifying shapes; XLA-fused jnp otherwise."""
    if flash_attention_available(q.shape, q.dtype):
        out, _ = _flash_fwd(_to_bh(q), _to_bh(k), _to_bh(v))
        return _from_bh(out, q.shape[0], q.shape[2])
    return _attention_jnp(q, k, v)


def _fwd(q, k, v):
    # The residuals carry checkpoint names (the identity outside a
    # jax.checkpoint): a policy that saves them by name keeps what the
    # backward kernels read, as they read it, and a rematerialised layer
    # runs no second flash forward. The output and its log-sum-exp share
    # a name: either alone buys nothing, the forward would run again for
    # the other.
    name = jax.ad_checkpoint.checkpoint_name
    if flash_attention_available(q.shape, q.dtype):
        B, H = q.shape[0], q.shape[2]
        qb, kb, vb = (name(_to_bh(t), n) for t, n in
                      ((q, "attn_q"), (k, "attn_k"), (v, "attn_v")))
        out, lse = _flash_fwd(qb, kb, vb)
        # every lane of a row of lse holds the row's value: keep one
        out, lse = name(out, "attn_out"), name(lse[..., 0], "attn_out")
        return _from_bh(out, B, H), (qb, kb, vb, out, lse)
    q, k, v = name(q, "attn_q"), name(k, "attn_k"), name(v, "attn_v")
    return _attention_jnp(q, k, v), (q, k, v)


def _bwd(res, g):
    if len(res) == 5:
        qb, kb, vb, out, lse = res
        B, H = g.shape[0], g.shape[2]
        gb = _to_bh(g)
        lse = jnp.broadcast_to(lse[..., None], lse.shape + (_LANES,))
        dq, dk, dv = _flash_bwd(qb, kb, vb, gb, out, lse)
        return (_from_bh(dq, B, H), _from_bh(dk, B, H), _from_bh(dv, B, H))
    q, k, v = res
    # recompute-based backward via jax.vjp of the jnp reference
    _, vjp_fn = jax.vjp(_attention_jnp, q, k, v)
    return vjp_fn(g)


causal_attention.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# autotuning (phi/kernels/autotune analog for the flash kernels)
# ---------------------------------------------------------------------------

def tune_causal_attention(B, S, H, D, dtype=jnp.bfloat16, budget_s=None,
                          iters=10, verbose=False):
    """Measure every legal (bq, bk) candidate for this attention shape on
    the current device and cache the fastest; subsequent traces of
    causal_attention at this (S, D, dtype) use the winner.

    Times forward + backward together (one fwd pallas_call + the two
    backward kernels), matching how training weights the kernels; ``iters``
    is the number of chained rounds per measurement. Runs eagerly — call
    before jit-compiling the train step. Returns the chosen (bq, bk), or
    None when tuning is disabled/disqualified everywhere.
    """
    from paddle_tpu.ops import autotune

    dtype = jnp.dtype(dtype)
    # new entries are recorded under the full context key; the cached
    # check walks the legacy chain too so committed shape-only caches
    # still short-circuit the sweep
    key = ["blocks", int(S), int(D)] + autotune.context_key(str(dtype))
    cached = autotune.lookup_chain("flash_attention",
                                   _flash_keys(S, D, dtype))
    if cached is not None:
        return tuple(cached)
    if not (_on_tpu() or _INTERPRET):
        return None

    BH = B * H
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(kk, (BH, S, D), dtype) * 0.5 for kk in ks)
    n_chain = max(1, int(iters))

    def time_candidate(cand):
        bq, bk = cand
        if S % bq or S % bk or S < bq:
            raise ValueError(f"({bq},{bk}) does not tile S={S}")

        # Chain n_chain fwd+bwd rounds inside one executable with a data
        # dependence between rounds, so one dispatch is amortized over
        # n_chain kernel launches.
        @jax.jit
        def chained(q, k, v, g):
            def body(qc, _):
                out, lse = _flash_fwd(qc, k, v, bq, bk)
                dq, _dk, _dv = _flash_bwd(qc, k, v, g, out, lse, bq, bk)
                return qc + dq * jnp.asarray(1e-6, qc.dtype), None
            qf, _ = lax.scan(body, q, None, length=n_chain)
            return jnp.sum(qf[0, 0])

        import time as _time
        chained(q, k, v, g).block_until_ready()  # compile + warmup
        reps = []
        for _ in range(5):
            t0 = _time.perf_counter()
            chained(q, k, v, g).block_until_ready()
            reps.append(_time.perf_counter() - t0)
        return min(reps) / n_chain

    return autotune.tune("flash_attention", key,
                         flash_candidates(S, D, dtype),
                         time_candidate, budget_s=budget_s, verbose=verbose,
                         verify_candidate=_verify_flash_candidate(
                             BH, S, D, dtype))


# ===========================================================================
# Fused decoder-block kernels
# ===========================================================================
#
# The llama decoder layer's hot path, fused into persistent Pallas kernels
# (MPK / Neptune-style block-level fusion — the RMSNorm / RoPE /
# projection / residual glue that XLA otherwise runs as separate fusions
# between kernel launches moves inside the kernels):
#
#   fused_attention_block:  y = x + attn(rope(rms(x)@wq), rope(rms(x)@wk),
#                                        rms(x)@wv) @ wo
#     Kernel A (_qkv_fused_kernel): RMSNorm (once per sequence block, in
#       VMEM scratch) + the three projections + RoPE — grid (B, S/bq, nh),
#       writing q/k/v in flattened [B, S, nh*D] layout so the flash stage
#       reads head slices without a transpose.
#     Kernel B (_attn_epi_kernel): resident flash attention per head +
#       the wo output projection and residual add in the epilogue — grid
#       (B, S/bq, nh) with the HEAD axis innermost, accumulating
#       attn_h @ wo[hD:(h+1)D, :] into a [bq, H] VMEM scratch that is
#       flushed (with the residual) when the last head finishes. The
#       head-innermost order keeps every revisit of the y output block on
#       consecutive grid steps, which is Mosaic's revisiting rule.
#     Backward: the O(S^2) core reuses the *verified* resident flash
#       backward kernel bodies unchanged, re-indexed over the flattened
#       layout (index maps slice heads: (bh//nh, i, bh%nh)); the
#       prologue/epilogue weight grads are jnp (pure MXU matmuls XLA
#       already runs at peak — the fusion win is the elementwise glue
#       and launch overhead, not the GEMMs).
#
#   fused_mlp_block:  y = x + (silu(rms(x)@wg) * (rms(x)@wu)) @ wd
#     One forward kernel, grid (B, S/bs, I/bi) with the INTERMEDIATE axis
#     innermost: RMSNorm once into scratch, then per intermediate block
#     gate/up matmul + SiLU + down-projection partial accumulated in a
#     [bs, H] scratch, residual added at the flush. Backward: a fused dx
#     kernel (recomputes gate/up per block, accumulates dxn, applies the
#     RMSNorm backward + residual in the epilogue) + jnp weight grads.
#
# RoPE inside a kernel: rotate_half needs a concat of two 64-lane slices,
# which Mosaic's lane tiling dislikes; instead the rotation is applied as
# a matmul against the constant +/-1 permutation matrix R (rot(x) = x @ R)
# built from iotas — MXU-friendly, exact (entries are 0/+-1), and
# guaranteed to lower.
#
# Both ops carry a custom_vjp with the jnp composition as the reference
# (and the fallback path when shapes/policy disqualify the kernels), and
# run under the Pallas interpreter on CPU — tier-1 checks fwd+bwd parity
# without hardware.


def _rms_norm_ref(x, w, eps):
    # mirrors models/llama.py::_rms_norm exactly (fp32 norm, cast to the
    # activation dtype BEFORE the weight multiply)
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(ms + eps)).astype(x.dtype) * w


def _rope_flat(x, sin, cos, D):
    """RoPE (neox rotate-half) over flattened-head [B, S, nh*D] layout —
    mirrors models/llama.py::_apply_rope per head."""
    B, S, H = x.shape
    xh = x.reshape(B, S, H // D, D)
    half = D // 2
    x1, x2 = xh[..., :half], xh[..., half:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    sin_ = sin[None, :, None, :].astype(x.dtype)
    cos_ = cos[None, :, None, :].astype(x.dtype)
    return (xh * cos_ + rot * sin_).reshape(B, S, H)


def _attention_block_jnp(x, ln, wq, wk, wv, wo, sin, cos, head_dim, eps):
    """jnp reference for fused_attention_block — the exact op sequence of
    the unfused decoder-layer attention sub-block (rmsnorm -> qkv -> rope
    -> causal attention -> wo -> residual)."""
    xn = _rms_norm_ref(x, ln, eps)
    q = _rope_flat(xn @ wq, sin, cos, head_dim)
    k = _rope_flat(xn @ wk, sin, cos, head_dim)
    v = xn @ wv
    B, S, H = x.shape
    nh = H // head_dim
    attn = _attention_jnp(q.reshape(B, S, nh, head_dim),
                          k.reshape(B, S, nh, head_dim),
                          v.reshape(B, S, nh, head_dim))
    return x + attn.reshape(B, S, H) @ wo


def _mlp_block_jnp(x, ln, wg, wu, wd, eps):
    """jnp reference for fused_mlp_block — the exact op sequence of the
    unfused decoder-layer MLP sub-block."""
    xn = _rms_norm_ref(x, ln, eps)
    return x + (jax.nn.silu(xn @ wg) * (xn @ wu)) @ wd


def fused_attn_block_specs(B, S, H, D, bq, bk):
    """(block_shape, array_shape) for every HBM operand of the fused
    attention block's kernels — consumed by the pallas_calls below, the
    candidate generator, and the shape unit tests."""
    nh = H // D
    xblk = ((1, bq, H), (B, S, H))
    headblk = ((1, bq, D), (B, S, H))
    headfull = ((1, S, D), (B, S, H))
    lse = ((1, 1, bq, _LANES), (B, nh, S, _LANES))
    lse_flat = ((1, bq, _LANES), (B * nh, S, _LANES))
    lse_flat_full = ((1, S, _LANES), (B * nh, S, _LANES))
    return {
        "qkv": {"in": [xblk, ((1, H), (1, H)),
                       ((H, D), (H, H)), ((H, D), (H, H)), ((H, D), (H, H)),
                       ((bq, D), (S, D)), ((bq, D), (S, D))],
                "out": [headblk, headblk, headblk]},
        "attn": {"in": [headblk, headfull, headfull, xblk, ((D, H), (H, H))],
                 "out": [xblk, headblk, lse]},
        "bwd_dq": {"in": [headblk, headfull, headfull, headblk, headblk,
                          lse_flat],
                   "out": [headblk]},
        "bwd_dkv": {"in": [headfull, ((1, bk, D), (B, S, H)),
                           ((1, bk, D), (B, S, H)), headfull, headfull,
                           lse_flat_full],
                    "out": [((1, bk, D), (B, S, H)),
                            ((1, bk, D), (B, S, H))]},
    }


def fused_mlp_block_specs(B, S, H, I, bs, bi):
    """(block_shape, array_shape) for the fused MLP kernels' operands."""
    xblk = ((1, bs, H), (B, S, H))
    return {
        "fwd": {"in": [xblk, ((1, H), (1, H)), ((H, bi), (H, I)),
                       ((H, bi), (H, I)), ((bi, H), (I, H))],
                "out": [xblk]},
        "bwd_dx": {"in": [xblk, ((1, H), (1, H)), ((H, bi), (H, I)),
                          ((H, bi), (H, I)), ((bi, H), (I, H)), xblk],
                   "out": [xblk]},
    }


def fused_attn_candidates(B, S, H, D, dtype=jnp.float32):
    """Legal-by-construction (bq, bk) candidates for the fused attention
    block: Mosaic-legal BlockSpecs (via mosaic_block_legal) AND the VMEM
    working set (resident k/v head, wo slice, x/y blocks, the [bq, H]
    epilogue accumulator) within budget."""
    from paddle_tpu.ops import autotune
    itemsize = jnp.dtype(dtype).itemsize

    def spec_fn(cand):
        bq, bk = cand
        if S % bq or S % bk or S < bq or bk % _LANES or H % D:
            return None
        vmem = (2 * S * D * itemsize        # resident k/v for this head
                + 3 * bq * H * itemsize     # x, y, (attn out rows)
                + D * H * itemsize          # wo slice
                + bq * H * 4                # f32 epilogue accumulator
                + bq * H * 4)               # f32 rmsnorm scratch (kernel A)
        if vmem > _VMEM_BUDGET:
            return None
        specs = fused_attn_block_specs(8, S, H, D, bq, bk)
        return [pair for groups in specs.values()
                for io in ("in", "out") for pair in groups[io]]

    pool = [(bq, bk) for bq in _POW2_BLOCKS for bk in _POW2_BLOCKS]
    bits = 8 * itemsize
    return autotune.legal_candidates(pool, spec_fn, dtype_bits=bits)


def fused_mlp_candidates(B, S, H, I, dtype=jnp.float32):
    """Legal-by-construction (bs, bi) candidates for the fused MLP block."""
    from paddle_tpu.ops import autotune
    itemsize = jnp.dtype(dtype).itemsize

    def spec_fn(cand):
        bs, bi = cand
        if S % bs or I % bi or S < bs or bi % _LANES:
            return None
        vmem = (2 * H * bi * itemsize       # wg, wu blocks
                + bi * H * itemsize         # wd block
                + 3 * bs * H * itemsize     # x, y/dy blocks
                + 2 * bs * H * 4            # f32 xn + accumulator scratch
                + 2 * bs * bi * 4)          # f32 gate/up intermediates
        if vmem > _VMEM_BUDGET:
            return None
        specs = fused_mlp_block_specs(8, S, H, I, bs, bi)
        return [pair for groups in specs.values()
                for io in ("in", "out") for pair in groups[io]]

    pool = [(bs, bi) for bs in _POW2_BLOCKS for bi in _POW2_BLOCKS]
    bits = 8 * itemsize
    return autotune.legal_candidates(pool, spec_fn, dtype_bits=bits)


def _fused_attn_config(S, H, D, dtype=None):
    """Active (bq, bk) for the fused attention block: the tuned winner
    when cached and still legal, else the first legal candidate, else
    None (shape disqualified)."""
    from paddle_tpu.ops import autotune
    cands = fused_attn_candidates(1, S, H, D, dtype or jnp.float32)
    if not cands:
        return None
    key = ["blocks", int(S), int(H), int(D)] + autotune.context_key(
        str(jnp.dtype(dtype)) if dtype is not None else None)
    cfg = autotune.lookup_chain("fused_attention", [key])
    if cfg is not None and tuple(int(c) for c in cfg) in cands:
        return tuple(int(c) for c in cfg)
    return cands[0]


def _fused_mlp_config(S, H, I, dtype=None):
    """Active (bs, bi) for the fused MLP block (same contract as
    _fused_attn_config)."""
    from paddle_tpu.ops import autotune
    cands = fused_mlp_candidates(1, S, H, I, dtype or jnp.float32)
    if not cands:
        return None
    key = ["blocks", int(S), int(H), int(I)] + autotune.context_key(
        str(jnp.dtype(dtype)) if dtype is not None else None)
    cfg = autotune.lookup_chain("fused_mlp", [key])
    if cfg is not None and tuple(int(c) for c in cfg) in cands:
        return tuple(int(c) for c in cfg)
    return cands[0]


def fused_attention_available(x_shape, head_dim, dtype=None):
    """Can the fused attention block run as Pallas kernels here?"""
    if not _kernels_enabled("fused_attention_block"):
        return False
    B, S, H = x_shape
    D = head_dim
    if H % D or D % 128:
        return False
    itemsize = jnp.dtype(dtype).itemsize if dtype is not None else 2
    if not _use_resident(S, D, itemsize):  # epilogue kernel is resident-only
        return False
    return _fused_attn_config(S, H, D, dtype) is not None


def fused_mlp_available(x_shape, inter_size, dtype=None):
    """Can the fused MLP block run as a Pallas kernel here?"""
    if not _kernels_enabled("fused_mlp_block"):
        return False
    B, S, H = x_shape
    return _fused_mlp_config(S, H, inter_size, dtype) is not None


def _rot_matrix(D, dtype):
    """The rotate-half permutation as a [D, D] +/-1 matrix: x @ R ==
    concat(-x2, x1). Built from iotas so it materializes inside the
    kernel (no lane-dim concat, which Mosaic's tiling rejects)."""
    half = D // 2
    ii = lax.broadcasted_iota(jnp.int32, (D, D), 0)
    jj = lax.broadcasted_iota(jnp.int32, (D, D), 1)
    return (ii == jj - half).astype(dtype) - (ii == jj + half).astype(dtype)


# ---------------------------------------------------------------------------
# fused attention: kernel A — RMSNorm + qkv projections + RoPE
# ---------------------------------------------------------------------------

def _qkv_fused_kernel(x_ref, ln_ref, wq_ref, wk_ref, wv_ref, sin_ref,
                      cos_ref, q_ref, k_ref, v_ref, xn_s, *, eps):
    from jax.experimental import pallas as pl
    h = pl.program_id(2)

    @pl.when(h == 0)
    def _norm():
        x32 = x_ref[0].astype(jnp.float32)
        ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        xn = (x32 * lax.rsqrt(ms + eps)).astype(x_ref.dtype) * ln_ref[...]
        xn_s[...] = xn.astype(jnp.float32)

    dt = q_ref.dtype
    xn = xn_s[...].astype(dt)
    D = q_ref.shape[-1]
    rot_m = _rot_matrix(D, dt)
    sin = sin_ref[...].astype(dt)
    cos = cos_ref[...].astype(dt)

    def proj(w_ref):
        return lax.dot(xn, w_ref[...],
                       preferred_element_type=jnp.float32).astype(dt)

    def rope(t):
        rot = lax.dot(t, rot_m, preferred_element_type=jnp.float32).astype(dt)
        return t * cos + rot * sin

    q_ref[0] = rope(proj(wq_ref))
    k_ref[0] = rope(proj(wk_ref))
    v_ref[0] = proj(wv_ref)


def _fused_qkv_proj(x, ln2d, wq, wk, wv, sin, cos, D, bq, eps):
    """x [B,S,H] -> q, k, v [B,S,H] (flattened heads, RoPE applied)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, S, H = x.shape
    nh = H // D
    specs = fused_attn_block_specs(B, S, H, D, bq, bq)["qkv"]
    by_x = lambda b, i, h: (b, i, 0)      # noqa: E731
    by_ln = lambda b, i, h: (0, 0)        # noqa: E731
    by_w = lambda b, i, h: (0, h)         # noqa: E731
    by_rope = lambda b, i, h: (i, 0)      # noqa: E731
    by_head = lambda b, i, h: (b, i, h)   # noqa: E731
    out_sds = jax.ShapeDtypeStruct((B, S, H), x.dtype)
    return _pallas_call(
        functools.partial(_qkv_fused_kernel, eps=eps),
        out_shape=(out_sds, out_sds, out_sds),
        grid=(B, S // bq, nh),
        in_specs=[
            pl.BlockSpec(specs["in"][0][0], by_x),
            pl.BlockSpec(specs["in"][1][0], by_ln),
            pl.BlockSpec(specs["in"][2][0], by_w),
            pl.BlockSpec(specs["in"][3][0], by_w),
            pl.BlockSpec(specs["in"][4][0], by_w),
            pl.BlockSpec(specs["in"][5][0], by_rope),
            pl.BlockSpec(specs["in"][6][0], by_rope),
        ],
        out_specs=tuple(pl.BlockSpec(s[0], by_head) for s in specs["out"]),
        scratch_shapes=[pltpu.VMEM((bq, H), jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
    )(x, ln2d, wq, wk, wv, sin, cos)


# ---------------------------------------------------------------------------
# fused attention: kernel B — resident flash + wo projection + residual
# ---------------------------------------------------------------------------

def _attn_epi_kernel(q_ref, k_ref, v_ref, x_ref, wo_ref, y_ref, attn_ref,
                     lse_ref, acc_s, *, bq, bk, scale):
    from jax.experimental import pallas as pl
    qi = pl.program_id(1)
    h = pl.program_id(2)
    nh = pl.num_programs(2)
    q = q_ref[0].astype(jnp.float32)          # [bq, D]
    D = q.shape[-1]
    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    n_kblocks = (qi * bq + bq + bk - 1) // bk  # causal: skip fully-masked

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(i * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * bk, bk), :].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        k_pos = i * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1)[:, None])
        p = jnp.exp(s - _rep_lanes(m_new[:, :1], bk))
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)[:, None]
        acc_new = acc * _rep_lanes(corr[:, :1], D) + lax.dot(
            p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, _LANES), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq, _LANES), jnp.float32)
    acc0 = jnp.zeros((bq, D), jnp.float32)
    m, l, acc = lax.fori_loop(0, n_kblocks, body, (m0, l0, acc0))
    attn = (acc / _rep_lanes(l[:, :1], D)).astype(attn_ref.dtype)
    attn_ref[0] = attn
    lse_ref[0, 0] = m + jnp.log(l)

    # epilogue: y = x + sum_h attn_h @ wo[h*D:(h+1)*D, :], accumulated in
    # f32 scratch across the (innermost) head axis
    @pl.when(h == 0)
    def _init():
        acc_s[...] = x_ref[0].astype(jnp.float32)

    acc_s[...] = acc_s[...] + lax.dot(attn, wo_ref[...],
                                      preferred_element_type=jnp.float32)

    @pl.when(h == nh - 1)
    def _flush():
        y_ref[0] = acc_s[...].astype(y_ref.dtype)


def _fused_attn_epilogue(qb, kb, vb, x, wo, D, bq, bk):
    """Flash attention over flattened heads + wo/residual epilogue.
    Returns (y [B,S,H], attn [B,S,H] pre-projection, lse [B,nh,S,128])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, S, H = x.shape
    nh = H // D
    scale = 1.0 / math.sqrt(D)
    specs = fused_attn_block_specs(B, S, H, D, bq, bk)["attn"]
    by_head = lambda b, i, h: (b, i, h)   # noqa: E731
    by_full = lambda b, i, h: (b, 0, h)   # noqa: E731
    by_x = lambda b, i, h: (b, i, 0)      # noqa: E731
    by_wo = lambda b, i, h: (h, 0)        # noqa: E731
    by_lse = lambda b, i, h: (b, h, i, 0)  # noqa: E731
    return _pallas_call(
        functools.partial(_attn_epi_kernel, bq=bq, bk=bk, scale=scale),
        out_shape=(jax.ShapeDtypeStruct((B, S, H), x.dtype),
                   jax.ShapeDtypeStruct((B, S, H), x.dtype),
                   jax.ShapeDtypeStruct((B, nh, S, _LANES), jnp.float32)),
        grid=(B, S // bq, nh),
        in_specs=[
            pl.BlockSpec(specs["in"][0][0], by_head),
            pl.BlockSpec(specs["in"][1][0], by_full),
            pl.BlockSpec(specs["in"][2][0], by_full),
            pl.BlockSpec(specs["in"][3][0], by_x),
            pl.BlockSpec(specs["in"][4][0], by_wo),
        ],
        out_specs=(pl.BlockSpec(specs["out"][0][0], by_x),
                   pl.BlockSpec(specs["out"][1][0], by_head),
                   pl.BlockSpec(specs["out"][2][0], by_lse)),
        scratch_shapes=[pltpu.VMEM((bq, H), jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
    )(qb, kb, vb, x, wo)


def _fused_flash_bwd_heads(qb, kb, vb, gb, ob, lse, D, bq, bk):
    """Flash backward over flattened-head [B, S, H] layout: the verified
    resident kernel BODIES run unchanged — only the index maps differ,
    slicing head h = bh % nh out of the last axis."""
    from jax.experimental import pallas as pl
    B, S, H = qb.shape
    nh = H // D
    scale = 1.0 / math.sqrt(D)
    lse_bh = lse.reshape(B * nh, S, _LANES)  # contiguous: free reshape
    specs = fused_attn_block_specs(B, S, H, D, bq, bk)

    blocked = lambda bh, i: (bh // nh, i, bh % nh)   # noqa: E731
    whole = lambda bh, i: (bh // nh, 0, bh % nh)     # noqa: E731
    lse_blk = lambda bh, i: (bh, i, 0)               # noqa: E731
    lse_full = lambda bh, i: (bh, 0, 0)              # noqa: E731

    dq_specs = specs["bwd_dq"]
    dq = _pallas_call(
        functools.partial(_flash_bwd_dq_kernel_resident, bq=bq, bk=bk,
                          scale=scale),
        out_shape=jax.ShapeDtypeStruct((B, S, H), qb.dtype),
        grid=(B * nh, S // bq),
        in_specs=[
            pl.BlockSpec(dq_specs["in"][0][0], blocked),   # q
            pl.BlockSpec(dq_specs["in"][1][0], whole),     # k
            pl.BlockSpec(dq_specs["in"][2][0], whole),     # v
            pl.BlockSpec(dq_specs["in"][3][0], blocked),   # g
            pl.BlockSpec(dq_specs["in"][4][0], blocked),   # o
            pl.BlockSpec(dq_specs["in"][5][0], lse_blk),   # lse
        ],
        out_specs=pl.BlockSpec(dq_specs["out"][0][0], blocked),
    )(qb, kb, vb, gb, ob, lse_bh)

    dkv_specs = specs["bwd_dkv"]
    dk, dv = _pallas_call(
        functools.partial(_flash_bwd_dkv_kernel_resident, bq=bq, bk=bk,
                          scale=scale, n_qblocks=S // bq),
        out_shape=(jax.ShapeDtypeStruct((B, S, H), kb.dtype),
                   jax.ShapeDtypeStruct((B, S, H), vb.dtype)),
        grid=(B * nh, S // bk),
        in_specs=[
            pl.BlockSpec(dkv_specs["in"][0][0], whole),    # q
            pl.BlockSpec(dkv_specs["in"][1][0], blocked),  # k
            pl.BlockSpec(dkv_specs["in"][2][0], blocked),  # v
            pl.BlockSpec(dkv_specs["in"][3][0], whole),    # g
            pl.BlockSpec(dkv_specs["in"][4][0], whole),    # o
            pl.BlockSpec(dkv_specs["in"][5][0], lse_full),  # lse
        ],
        out_specs=(pl.BlockSpec(dkv_specs["out"][0][0], blocked),
                   pl.BlockSpec(dkv_specs["out"][1][0], blocked)),
    )(qb, kb, vb, gb, ob, lse_bh)
    return dq, dk, dv


def _fused_attention_fwd_impl(cfgt, x, ln, wq, wk, wv, wo, sin, cos):
    head_dim, eps, bq, bk = cfgt
    ln2d = ln.reshape(1, -1)
    qb, kb, vb = _fused_qkv_proj(x, ln2d, wq, wk, wv, sin, cos,
                                 head_dim, bq, eps)
    y, attn, lse = _fused_attn_epilogue(qb, kb, vb, x, wo, head_dim, bq, bk)
    return y, (x, ln, wq, wk, wv, wo, sin, cos, qb, kb, vb, attn, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_attention_call(cfgt, x, ln, wq, wk, wv, wo, sin, cos):
    y, _ = _fused_attention_fwd_impl(cfgt, x, ln, wq, wk, wv, wo, sin, cos)
    return y


def _fused_attention_fwd(cfgt, x, ln, wq, wk, wv, wo, sin, cos):
    return _fused_attention_fwd_impl(cfgt, x, ln, wq, wk, wv, wo, sin, cos)


def _fused_attention_bwd(cfgt, res, dy):
    head_dim, eps, bq, bk = cfgt
    x, ln, wq, wk, wv, wo, sin, cos, qb, kb, vb, attn, lse = res
    # epilogue transpose (jnp: plain MXU matmuls)
    dwo = jnp.einsum("bsi,bsj->ij", attn, dy)
    gb = jnp.einsum("bsj,ij->bsi", dy, wo)
    # the O(S^2) core: the flash backward Pallas kernels
    dqb, dkb, dvb = _fused_flash_bwd_heads(qb, kb, vb, gb, attn, lse,
                                           head_dim, bq, bk)

    # prologue transpose via jax.vjp of the jnp prologue: rmsnorm/rope/
    # projection weight grads are pure matmul+elementwise work XLA runs
    # at peak; hand-fusing them buys nothing over the flash core win
    def prologue(x_, ln_, wq_, wk_, wv_, sin_, cos_):
        xn = _rms_norm_ref(x_, ln_, eps)
        return (_rope_flat(xn @ wq_, sin_, cos_, head_dim),
                _rope_flat(xn @ wk_, sin_, cos_, head_dim),
                xn @ wv_)

    _, pvjp = jax.vjp(prologue, x, ln, wq, wk, wv, sin, cos)
    dx_p, dln, dwq, dwk, dwv, dsin, dcos = pvjp((dqb, dkb, dvb))
    return dy + dx_p, dln, dwq, dwk, dwv, dwo, dsin, dcos


_fused_attention_call.defvjp(_fused_attention_fwd, _fused_attention_bwd)


def fused_attention_block(x, ln, wq, wk, wv, wo, sin, cos, *, head_dim,
                          eps=1e-6):
    """Fused decoder-layer attention sub-block:
    ``x + attn(rope(rms(x) @ wq), rope(rms(x) @ wk), rms(x) @ wv) @ wo``.

    x: [B, S, H]; wq/wk/wv/wo: [H, H]; ln: [H]; sin/cos: [S, head_dim].
    Pallas kernels (qkv-prologue + flash-with-epilogue) on TPU / under
    the interpreter for qualifying shapes; the jnp reference composition
    otherwise. Differentiable either way (custom_vjp reusing the flash
    backward kernels on the fused path)."""
    if fused_attention_available(x.shape, head_dim, x.dtype):
        bq, bk = _fused_attn_config(x.shape[1], x.shape[2], head_dim,
                                    x.dtype)
        return _fused_attention_call((head_dim, float(eps), bq, bk),
                                     x, ln, wq, wk, wv, wo, sin, cos)
    return _attention_block_jnp(x, ln, wq, wk, wv, wo, sin, cos,
                                head_dim, eps)


# ---------------------------------------------------------------------------
# fused MLP block
# ---------------------------------------------------------------------------

def _mlp_fused_kernel(x_ref, ln_ref, wg_ref, wu_ref, wd_ref, y_ref,
                      xn_s, acc_s, *, eps):
    from jax.experimental import pallas as pl
    ii = pl.program_id(2)
    n_i = pl.num_programs(2)

    @pl.when(ii == 0)
    def _init():
        x32 = x_ref[0].astype(jnp.float32)
        ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        xn = (x32 * lax.rsqrt(ms + eps)).astype(x_ref.dtype) * ln_ref[...]
        xn_s[...] = xn.astype(jnp.float32)
        acc_s[...] = jnp.zeros_like(acc_s)

    xn = xn_s[...].astype(x_ref.dtype)
    g = lax.dot(xn, wg_ref[...], preferred_element_type=jnp.float32)
    u = lax.dot(xn, wu_ref[...], preferred_element_type=jnp.float32)
    a = (jax.nn.silu(g) * u).astype(x_ref.dtype)
    acc_s[...] = acc_s[...] + lax.dot(a, wd_ref[...],
                                      preferred_element_type=jnp.float32)

    @pl.when(ii == n_i - 1)
    def _flush():
        y_ref[0] = (x_ref[0].astype(jnp.float32)
                    + acc_s[...]).astype(y_ref.dtype)


def _mlp_bwd_dx_kernel(x_ref, ln_ref, wg_ref, wu_ref, wd_ref, dy_ref,
                       dx_ref, xn_s, dacc_s, *, eps):
    from jax.experimental import pallas as pl
    ii = pl.program_id(2)
    n_i = pl.num_programs(2)

    @pl.when(ii == 0)
    def _init():
        x32 = x_ref[0].astype(jnp.float32)
        ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        xn = (x32 * lax.rsqrt(ms + eps)).astype(x_ref.dtype) * ln_ref[...]
        xn_s[...] = xn.astype(jnp.float32)
        dacc_s[...] = jnp.zeros_like(dacc_s)

    xn = xn_s[...].astype(x_ref.dtype)
    g = lax.dot(xn, wg_ref[...], preferred_element_type=jnp.float32)
    u = lax.dot(xn, wu_ref[...], preferred_element_type=jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    # da = dy @ wd_blk^T   [bs, bi]
    da = lax.dot_general(dy, wd_ref[...].astype(jnp.float32),
                         (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    sg = jax.nn.sigmoid(g)
    silu_g = g * sg
    dsilu = sg + g * sg * (1.0 - sg)
    dg = da * u * dsilu
    du = da * silu_g
    # dxn += dg @ wg_blk^T + du @ wu_blk^T
    dacc_s[...] = dacc_s[...] + lax.dot_general(
        dg, wg_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) + lax.dot_general(
        du, wu_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ii == n_i - 1)
    def _flush():
        # RMSNorm backward + residual, fused into the last grid step:
        # y = x + f(w * n(x)) with n(x) = x * rsqrt(mean(x^2) + eps)
        # => dx_i = dy_i + r * dz_i - x_i * <dz, x> * r^3 / H
        x32 = x_ref[0].astype(jnp.float32)
        Hdim = x32.shape[-1]
        ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        r = lax.rsqrt(ms + eps)
        dz = dacc_s[...] * ln_ref[...].astype(jnp.float32)
        inner = jnp.sum(dz * x32, axis=-1, keepdims=True)
        dxn_x = dz * r - x32 * (inner * r * r * r / Hdim)
        dx_ref[0] = (dy_ref[0].astype(jnp.float32)
                     + dxn_x).astype(dx_ref.dtype)


def _fused_mlp_pallas(kernel, inputs, out_dtype, S, H, I, bs, bi,
                      which):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B = inputs[0].shape[0]
    specs = fused_mlp_block_specs(B, S, H, I, bs, bi)[which]
    by_x = lambda b, i, ii: (b, i, 0)    # noqa: E731
    by_ln = lambda b, i, ii: (0, 0)      # noqa: E731
    by_gu = lambda b, i, ii: (0, ii)     # noqa: E731
    by_d = lambda b, i, ii: (ii, 0)      # noqa: E731
    maps = [by_x, by_ln, by_gu, by_gu, by_d] + \
        ([by_x] if which == "bwd_dx" else [])
    return _pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, S, H), out_dtype),
        grid=(B, S // bs, I // bi),
        in_specs=[pl.BlockSpec(s[0], m)
                  for s, m in zip(specs["in"], maps)],
        out_specs=pl.BlockSpec(specs["out"][0][0], by_x),
        scratch_shapes=[pltpu.VMEM((bs, H), jnp.float32),
                        pltpu.VMEM((bs, H), jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
    )(*inputs)


def _fused_mlp_fwd_impl(cfgt, x, ln, wg, wu, wd):
    eps, bs, bi = cfgt
    B, S, H = x.shape
    I = wg.shape[1]
    y = _fused_mlp_pallas(
        functools.partial(_mlp_fused_kernel, eps=eps),
        (x, ln.reshape(1, -1), wg, wu, wd), x.dtype, S, H, I, bs, bi,
        "fwd")
    return y, (x, ln, wg, wu, wd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_mlp_call(cfgt, x, ln, wg, wu, wd):
    y, _ = _fused_mlp_fwd_impl(cfgt, x, ln, wg, wu, wd)
    return y


def _fused_mlp_bwd(cfgt, res, dy):
    eps, bs, bi = cfgt
    x, ln, wg, wu, wd = res
    B, S, H = x.shape
    I = wg.shape[1]
    # dx: fused Pallas kernel (recompute gate/up per intermediate block,
    # accumulate dxn, RMSNorm backward + residual in the epilogue)
    dx = _fused_mlp_pallas(
        functools.partial(_mlp_bwd_dx_kernel, eps=eps),
        (x, ln.reshape(1, -1), wg, wu, wd, dy), x.dtype, S, H, I, bs, bi,
        "bwd_dx")

    # weight + ln grads via jax.vjp of the jnp composition with x fixed:
    # these are the big einsums XLA already runs at MXU peak
    def wfn(ln_, wg_, wu_, wd_):
        xn = _rms_norm_ref(x, ln_, eps)
        return (jax.nn.silu(xn @ wg_) * (xn @ wu_)) @ wd_

    _, wvjp = jax.vjp(wfn, ln, wg, wu, wd)
    dln, dwg, dwu, dwd = wvjp(dy)
    return dx, dln, dwg, dwu, dwd


_fused_mlp_call.defvjp(_fused_mlp_fwd_impl, _fused_mlp_bwd)


def fused_mlp_block(x, ln, w_gate, w_up, w_down, *, eps=1e-6):
    """Fused decoder-layer MLP sub-block:
    ``x + (silu(rms(x) @ w_gate) * (rms(x) @ w_up)) @ w_down``.

    One persistent Pallas kernel forward (RMSNorm + gate/up + SiLU + down
    + residual), fused dx kernel backward; recompute-based (saves only
    the primal inputs — remat-friendly). jnp reference composition when
    the shape/policy disqualifies the kernel."""
    if fused_mlp_available(x.shape, w_gate.shape[1], x.dtype):
        bs, bi = _fused_mlp_config(x.shape[1], x.shape[2],
                                   w_gate.shape[1], x.dtype)
        return _fused_mlp_call((float(eps), bs, bi),
                               x, ln, w_gate, w_up, w_down)
    return _mlp_block_jnp(x, ln, w_gate, w_up, w_down, eps)


# ---------------------------------------------------------------------------
# fused-op tuning + parity registry
# ---------------------------------------------------------------------------

def tune_fused_blocks(B, S, H, D, I, dtype=jnp.bfloat16, budget_s=None,
                      iters=10, verbose=False):
    """Measure the legal (bq, bk) / (bs, bi) candidates for the fused
    attention and MLP blocks at this decoder shape and cache the winners
    (ops "fused_attention" / "fused_mlp"). Times fwd+bwd together via a
    chained scan, like tune_causal_attention. Returns
    {"fused_attention": cfg|None, "fused_mlp": cfg|None}."""
    from paddle_tpu.ops import autotune

    dtype = jnp.dtype(dtype)
    results = {}
    if not (_on_tpu() or _INTERPRET):
        return {"fused_attention": None, "fused_mlp": None}
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    x = (jax.random.normal(ks[0], (B, S, H), dtype) * 0.5)
    dy = (jax.random.normal(ks[1], (B, S, H), dtype) * 0.5)
    ln = jnp.ones((H,), dtype)
    wq, wk, wv, wo = (jax.random.normal(kk, (H, H), dtype) * 0.02
                      for kk in ks[2:6])
    half = D // 2
    ang = jnp.concatenate([jnp.arange(half, dtype=jnp.float32)] * 2)
    pos = jnp.arange(S, dtype=jnp.float32)[:, None] * (ang + 1.0)[None, :]
    sin, cos = jnp.sin(pos), jnp.cos(pos)
    n_chain = max(1, int(iters))

    def timed(fn, *args):
        import time as _time

        @jax.jit
        def chained(*a):
            def body(c, _):
                return c + fn(c, *a[1:]) * jnp.asarray(1e-6, c.dtype), None
            out, _ = lax.scan(body, a[0], None, length=n_chain)
            return jnp.sum(out[0, 0])

        chained(*args).block_until_ready()  # compile + warmup
        reps = []
        for _ in range(5):
            t0 = _time.perf_counter()
            chained(*args).block_until_ready()
            reps.append(_time.perf_counter() - t0)
        return min(reps) / n_chain

    def time_attn(cand):
        bq, bk = cand

        def step(xc, *rest):
            f = lambda t: _fused_attention_call(  # noqa: E731
                (D, 1e-6, bq, bk), t, ln, wq, wk, wv, wo, sin, cos)
            y, pull = jax.vjp(f, xc)
            (dx,) = pull(dy)
            return y + dx

        return timed(step, x)

    def verify_attn(cand):
        from paddle_tpu.analysis import kernel_checks as _kc
        bq, bk = cand
        found = _kc.verify_kernel(
            lambda t: _fused_attention_call(  # noqa: E731
                (D, 1e-6, bq, bk), t, ln, wq, wk, wv, wo, sin, cos),
            jax.ShapeDtypeStruct((B, S, H), dtype),
            name=f"fused_attention[{bq}x{bk}]")
        return [f"{f.rule}: {f.message}" for f in found
                if f.severity == "error"]

    akey = ["blocks", int(S), int(H), int(D)] + autotune.context_key(
        str(dtype))
    results["fused_attention"] = autotune.tune(
        "fused_attention", akey, fused_attn_candidates(B, S, H, D, dtype),
        time_attn, budget_s=budget_s, verbose=verbose,
        verify_candidate=verify_attn)

    wg = jax.random.normal(ks[6], (H, I), dtype) * 0.02
    wu = jax.random.normal(ks[7], (H, I), dtype) * 0.02
    wd = jnp.swapaxes(wu, 0, 1) * 1.0

    def time_mlp(cand):
        bs, bi = cand

        def step(xc):
            f = lambda t: _fused_mlp_call(  # noqa: E731
                (1e-6, bs, bi), t, ln, wg, wu, wd)
            y, pull = jax.vjp(f, xc)
            (dx,) = pull(dy)
            return y + dx

        return timed(step, x)

    def verify_mlp(cand):
        from paddle_tpu.analysis import kernel_checks as _kc
        bs, bi = cand
        found = _kc.verify_kernel(
            lambda t: _fused_mlp_call(  # noqa: E731
                (1e-6, bs, bi), t, ln, wg, wu, wd),
            jax.ShapeDtypeStruct((B, S, H), dtype),
            name=f"fused_mlp[{bs}x{bi}]")
        return [f"{f.rule}: {f.message}" for f in found
                if f.severity == "error"]

    mkey = ["blocks", int(S), int(H), int(I)] + autotune.context_key(
        str(dtype))
    results["fused_mlp"] = autotune.tune(
        "fused_mlp", mkey, fused_mlp_candidates(B, S, H, I, dtype),
        time_mlp, budget_s=budget_s, verbose=verbose,
        verify_candidate=verify_mlp)
    return results


def fused_parity_cases():
    """(name, fused_fn, reference_fn, make_args) for the fused decoder-
    block kernels — the parity registry ops/codegen.py re-exports and
    tests/test_pallas_fused.py sweeps (fwd and bwd, interpret mode)."""
    D = 128

    def attn_args(key, B=1, S=256, H=256, dtype=jnp.float32):
        ks = jax.random.split(key, 7)
        x = jax.random.normal(ks[0], (B, S, H), dtype) * 0.5
        ln = 1.0 + 0.1 * jax.random.normal(ks[1], (H,), dtype)
        wq, wk, wv, wo = (jax.random.normal(kk, (H, H), dtype) * 0.05
                          for kk in ks[2:6])
        half = D // 2
        inv = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32)
                                 / half))
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
        emb = jnp.concatenate([ang, ang], axis=-1)
        return (x, ln, wq, wk, wv, wo, jnp.sin(emb), jnp.cos(emb))

    def mlp_args(key, B=1, S=256, H=256, I=512, dtype=jnp.float32):
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (B, S, H), dtype) * 0.5
        ln = 1.0 + 0.1 * jax.random.normal(ks[1], (H,), dtype)
        wg = jax.random.normal(ks[2], (H, I), dtype) * 0.05
        wu = jax.random.normal(ks[3], (H, I), dtype) * 0.05
        wd = jax.random.normal(ks[4], (I, H), dtype) * 0.05
        return (x, ln, wg, wu, wd)

    return [
        ("fused_attention_block",
         functools.partial(fused_attention_block, head_dim=D, eps=1e-6),
         functools.partial(_attention_block_jnp, head_dim=D, eps=1e-6),
         attn_args),
        ("fused_mlp_block",
         functools.partial(fused_mlp_block, eps=1e-6),
         functools.partial(_mlp_block_jnp, eps=1e-6),
         mlp_args),
    ]


# ---------------------------------------------------------------------------
# Ragged paged attention (the TPU serving kernel)
# ---------------------------------------------------------------------------
#
# One kernel serves a mixed prefill+decode batch over a block-table
# paged KV cache (PAPERS.md: "Ragged Paged Attention").  Layout:
#
#   q            [R, nkv, Tr, d]   Tr = Tc * rep fixed per-request token
#                                  slots; request r contributes
#                                  q_lens[r] real tokens (rep q-head
#                                  slots each), the rest is padding
#   k/v pools    [L, nkv, P, page, d] every layer's pool in one stacked
#                                  buffer, left in HBM: the kernel copies
#                                  the pages it needs out of it itself and
#                                  never sees a slice of it
#   layer        [1] i32           which layer of the stack to attend over
#   block_tables [R, Bmax] i32     logical kv-block j of request r lives
#                                  in pool page block_tables[r, j];
#                                  unused slots hold 0 (page 0 is the
#                                  allocator's reserved null page)
#   seq_lens     [R] i32           total kv length incl. current chunk
#   q_lens       [R] i32           tokens in the current chunk (0 =
#                                  inactive slot, 1 = decode, >1 =
#                                  chunked prefill)
#
# Grid (R,): one grid step is one request row, all its kv heads.  The four
# scalar operands ride in via ``pltpu.PrefetchScalarGridSpec`` (SMEM); q
# and the output are (1, nkv, Tr, d) blocks; the pools have no block.  A
# row walks only its LIVE pages, ``ceil(seq_len / page)`` of the table's
# Bmax entries (none when q_len == 0), ``G`` pages a turn of an in-kernel
# loop: page ``tbl[r, j]`` of all nkv heads is one strided copy out of
# ``pool[layer, :, page]`` into one of two VMEM slots, and group i + 1 is
# in flight while group i is computed.  The row's last turn starts the
# first group of the next row, so only the call's first fetch is exposed.
# Per group and head: QK^T over the G x page keys at once, the
# online-softmax flash recurrence in float32, PV.  Table entries past a
# row's length and the tables of idle rows cost nothing: no grid step, no
# copy, no compute.  Padding rows (tok >= q_lens[r]) are flushed as exact
# zeros.  ``G`` comes from the shapes (``_rpa_group_pages``).

_NEG_BIG = -1e30  # finite mask value: -inf would NaN fully-masked rows
_RPA_Q_BLOCK = 256  # query rows of a block, where a latent row has more


def _rep_cols(col, n):
    """[R, 1] -> [R, n] broadcast.  Uses the lane-tiling idiom when n is
    a multiple of the 128-lane width (the only Mosaic-legal case on
    TPU); any other width is interpret/jnp-only and plain broadcast."""
    if n % _LANES == 0:
        return _rep_lanes(col, n)
    return jnp.broadcast_to(col, (col.shape[0], n))


def _rpa_row_bytes(nkv, Tr, d, itemsize):
    """What a row takes of VMEM whatever the walk's group is: its q and o
    blocks (the pipeline keeps two of each) and the float32 statistics and
    accumulator of every head."""
    return 4 * nkv * Tr * d * itemsize + nkv * Tr * (2 * _LANES + d) * 4


def _rpa_group_pages(nkv, Tr, d, page, itemsize, Bmax, qblk=None,
                     budget=None):
    """``G``, the pages of one DMA group: the largest power of two, at
    most the table's width, whose working set fits ``budget`` (the
    call's, where it grants one; else ``_VMEM_BUDGET``) beside what a row
    needs whatever G is (``_rpa_row_bytes``: the whole row's, blocks or
    not).  A page of the group costs K and V of all heads in both slots,
    and eight 4-byte tiles of the rows a turn computes, ``[qblk, page]``
    where the row is walked ``qblk`` query rows at a time, else ``[Tr,
    page]`` (token, column and horizon, which live through the loop; a
    head's mask, scores, exponent, probabilities and their scaled copy).
    Fewer, fatter turns win while the copies or the turns' own cost bound
    the row (8 kv heads, Tr 32: 7.1 ms a step of the chat cell's shapes
    at G 8, 7.3 at 4, 10 at 2); where the scores bound it, a wide group's
    dead key columns cost more than its turns save (1 kv head, Tr 320:
    1.45 ms at G 8, 1.81 at 16), which the tiles' share of the budget
    stands for (PERF.md section 6, PR 28)."""
    room = (_VMEM_BUDGET if budget is None else budget) \
        - _rpa_row_bytes(nkv, Tr, d, itemsize)
    per_page = 4 * nkv * page * d * itemsize + 8 * (qblk or Tr) * page * 4
    fit = max(1, min(room // per_page, Bmax))
    return 1 << (int(fit).bit_length() - 1)


def _rpa_short_rows(rep, Tr):
    """The query rows of the walk's few-row turn: the fewest whole 16-row
    tiles that hold one token's ``rep`` query heads, or None where that is
    all ``Tr`` rows (every ``[R, 1]`` program).  A row with ``q_len x rep
    <= short`` computes those rows alone and not its padding: at rep 16
    and ``Tr`` 256 a one-token row, at rep 20 and 320 too (32 rows), at
    rep 4 and 64 a row of up to 4 tokens, at rep 2 and 32 up to 8."""
    short = -(-rep // 16) * 16
    return short if short < Tr else None


def _rpa_walk(tbl_ref, lens_ref, qlens_ref, layer_ref, ksc_ref, vsc_ref,
              q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, slot_ref, m_s,
              l_s, acc_s, *, page, rep, scale, window=None, qblk=None):
    """Grid step r: request r's q rows, all kv heads, against its live kv
    pages in layer ``layer_ref[0]`` of the stacked pools, ``G`` pages a
    loop turn (``kbuf``/``vbuf`` are ``[2, nkv, G * page, d]``: two slots
    for the double buffer).  With ``ksc_ref``/``vsc_ref`` (int8 pools)
    a page's dequant scale multiplies its score columns, and its
    probability columns before PV: the same products as a dequantized
    page gives, taken after the copy landed and off the [page, d] tile.

    With ``window = W`` a q row at position p sees the keys ``p - W < j <=
    p``: the row's walk starts at the page of the first key its first q row
    sees, ``max(0, kvlen - qlen - W + 1) // page``, not at page 0, and the
    mask gains the lower bound.  The pages before are never copied, so a
    table may map them anywhere (a ring of pages: ``models/phi4flash.py``).
    ``window=None`` traces exactly the walk from page 0.

    A row that feeds at most ``short`` query rows (``_rpa_short_rows``:
    whole 16-row tiles, e.g. a decode row inside the chunk bucket at 16
    query heads a K/V head) computes those rows and not its ``Tr - short``
    padding rows, under a mask built for them alone (the row's first page
    and the window's lower bound with it), on dense and latent pools
    alike.

    With ``v_hbm`` and ``vbuf`` None the pages are LATENT: a token's one
    vector is its key, and its first ``o_ref.shape[-1]`` lanes are its value
    (``_rpa_kernel_latent``).  A page is copied once and V is a slice of
    the K tile already in VMEM; the probabilities go to the MXU in the
    pages' dtype, as the scores' operands do; and a row of more query rows
    than ``qblk`` takes them a block at a time.  Its one head slices a
    few-row turn out of the whole mask where it has no blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    r = pl.program_id(0)
    R = pl.num_programs(0)
    Bmax = tbl_ref.shape[1]
    nkv, Tr, d = q_ref.shape[1:]
    latent = v_hbm is None
    dv = o_ref.shape[-1]         # V's lanes: d, or the head of a latent
    # Tr = chunk x rep rows a K/V head: a row of few tokens (a decode row in
    # the chunk bucket) would pay the whole rectangle for its padding.
    # `short` is the fewest query rows a turn may take, dense or latent
    short = _rpa_short_rows(rep, Tr)
    span = kbuf.shape[2]
    G = span // page
    layer = layer_ref[0]

    def live_pages(row):
        return jnp.where(
            qlens_ref[row] > 0,
            jnp.minimum(pl.cdiv(lens_ref[row], page), Bmax), 0)

    def first_page(row):
        """The first page row ``row`` walks (None: page 0, no window)."""
        if window is None:
            return None
        return jnp.maximum(
            lens_ref[row] - qlens_ref[row] - window + 1, 0) // page

    def walked(row):
        """How many pages row ``row`` walks, from its first page on."""
        if window is None:
            return live_pages(row)
        return jnp.maximum(live_pages(row) - first_page(row), 0)

    def page_at(first, k):
        """The table column of the k-th page of a walk."""
        return k if first is None else first + k

    def copies(page_of, g, slot):
        """K's and V's copy of one page into place g of ``slot``."""
        for hbm, buf, which in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1))[
                :1 if latent else 2]:
            yield pltpu.make_async_copy(
                hbm.at[layer, :, page_of],
                buf.at[slot, :, pl.ds(pl.multiple_of(g * page, page), page)],
                sem.at[slot, which])

    def in_group(i, live):
        return jnp.clip(live - i * G, 0, G)      # live pages of group i

    def start(row, first, i, slot, live):
        def one(g, carry):
            for copy in copies(tbl_ref[row, page_at(first, i * G + g)], g,
                               slot):
                copy.start()
            return carry
        lax.fori_loop(0, in_group(i, live), one, 0)

    def wait(i, slot, live):
        def one(g, carry):
            for copy in copies(0, g, slot):      # a wait counts the bytes
                copy.wait()
            return carry
        lax.fori_loop(0, in_group(i, live), one, 0)

    live, first = walked(r), first_page(r)
    n = pl.cdiv(live, G)
    nxt = jnp.minimum(r + 1, R - 1)
    live_nxt, first_nxt = jnp.where(r + 1 < R, walked(nxt), 0), \
        first_page(nxt)

    def either(more, mine, next_rows):
        """This row's or the next row's first page, by ``more``."""
        return None if mine is None else jnp.where(more, mine, next_rows)

    @pl.when(r == 0)
    def _first_row():
        # a masked key's probability is an exact 0, and 0 * NaN is not:
        # what no copy has overwritten yet must be finite
        kbuf[...] = jnp.zeros_like(kbuf)
        if not latent:
            vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        start(r, first, 0, 0, live)

    slot0 = slot_ref[0]      # where this row's first group lands
    m_s[...] = jnp.full_like(m_s, _NEG_BIG)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)
    kvlen = lens_ref[r]
    qlen = qlens_ref[r]

    @pl.when(n == 0)
    def _idle_row():
        start(nxt, first_nxt, 0, slot0, live_nxt)

    if qblk is None:
        tok = lax.broadcasted_iota(jnp.int32, (Tr, span), 0) // rep
        col = lax.broadcasted_iota(jnp.int32, (Tr, span), 1)
        # a q row sees the keys up to its own position; a padding row none
        horizon = jnp.where(tok < qlen, kvlen - qlen + tok, -1)
        # a key column's position, less the group's offset: the walk starts
        # at page 0 unless a window moved it
        pos = col if window is None else first * page + col
    elif window is not None or not latent:
        raise ValueError("query blocks are the latent walk's, from page 0")

    def block_mask(i, lo, n):
        """The mask of group i for the query rows ``lo .. lo + n`` alone:
        a key column's position from the walk's first page on, under the
        window's lower bound where there is one."""
        tok = (lo + lax.broadcasted_iota(jnp.int32, (n, span), 0)) // rep
        col = lax.broadcasted_iota(jnp.int32, (n, span), 1)
        if first is not None:
            col = first * page + col
        key = i * span + col
        horizon = jnp.where(tok < qlen, kvlen - qlen + tok, -1)
        seen = key <= horizon
        if window is not None:
            seen &= key > horizon - window
        return seen

    def group(i, carry):
        slot = (slot0 + i) % 2
        # the next group of this row, or the first of the next row
        more = i + 1 < n
        start(jnp.where(more, r, nxt), either(more, first, first_nxt),
              jnp.where(more, i + 1, 0), 1 - slot,
              jnp.where(more, live, live_nxt))
        wait(i, slot, live)

        def whole_mask():
            """The mask of group i for all ``Tr`` query rows."""
            mask = i * span + pos <= horizon
            if window is not None:
                mask &= i * span + pos > horizon - window
            return mask

        # where every row takes the whole rectangle, and for the latent
        # walk's one head, the mask is made before the turns; the dense
        # walk's few-row turn builds its own and the whole-row turn this one
        mask = whole_mask() if qblk is None and (short is None or latent) \
            else None

        def page_scales(sc_ref, h):
            """[1, span]: each key column's page's dequant scale."""
            out = jnp.zeros((1, span), jnp.float32)
            for g in range(G):
                pg = tbl_ref[r, jnp.minimum(page_at(first, i * G + g),
                                            Bmax - 1)]
                out = jnp.where(col[:1] // page == g, sc_ref[h, pg], out)
            return out

        def head(h, seen, rows=None, lo=0):
            """Head h's turn on the group under the mask ``seen``; ``rows``
            (static): only the ``rows`` query rows from ``lo`` on, the
            others being padding or another block's."""
            at = (h,) if rows is None else (h, slice(lo, lo + rows))
            q = q_ref[(0,) + at]                         # [Tr, d]
            k = kbuf[slot, h]                            # [span, d]
            v = k[:, :dv] if latent else vbuf[slot, h].astype(jnp.float32)
            if ksc_ref is not None:
                k = k.astype(q.dtype)    # int8: exact in bf16 and float32
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if ksc_ref is not None:
                s = s * page_scales(ksc_ref, h)
            s = jnp.where(seen, s, _NEG_BIG)
            m = m_s[at]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1)[:, None])
            # a masked score is _NEG_BIG under a real row's running max
            # and its exp an exact 0; padding rows, whose every score is
            # masked, gather ones here and are zeroed at the flush
            p = jnp.exp(s - _rep_cols(m_new[:, :1], span))
            corr = jnp.exp(m - m_new)
            l_s[at] = l_s[at] * corr + jnp.sum(p, axis=-1)[:, None]
            m_s[at] = m_new
            if vsc_ref is not None:
                p = p * page_scales(vsc_ref, h)
            acc_s[at] = (acc_s[at] * _rep_cols(corr[:, :1], dv)
                         + lax.dot(p.astype(v.dtype) if latent else p, v,
                                   preferred_element_type=jnp.float32))

        # unrolled: the scheduler overlaps one head's softmax with the
        # next one's matmuls (7.3 against 8.6 ms a step as a rolled loop,
        # the chat cell's shapes; PERF.md section 6, PR 28)
        if short is None:
            for h in range(nkv):
                head(h, mask)
            return carry

        # a row of few tokens (a decode row inside the chunk bucket) takes
        # its first `short` query rows through the MXU and the softmax, and
        # not all Tr
        @pl.when(qlen * rep <= short)
        def _few_rows():
            seen = block_mask(i, 0, short) if mask is None else mask[:short]
            for h in range(nkv):
                head(h, seen, short)

        if qblk is None:
            @pl.when(qlen * rep > short)
            def _every_row():
                seen = whole_mask() if mask is None else mask
                for h in range(nkv):
                    head(h, seen)
            return carry

        for lo in range(0, Tr, qblk):
            @pl.when((qlen * rep > short) & (qlen * rep > lo))
            def _block_of_rows(lo=lo):
                for h in range(nkv):
                    head(h, block_mask(i, lo, qblk), qblk, lo)
        return carry

    lax.fori_loop(0, n, group, 0)
    slot_ref[0] = (slot0 + n) % 2
    real = lax.broadcasted_iota(jnp.int32, (Tr, dv), 0) // rep < qlen

    def flush(h, carry):
        l = l_s[h]
        denom = jnp.where(l == 0.0, 1.0, l)      # an idle row: 0 / 1
        o_ref[0, h] = jnp.where(
            real, acc_s[h] / _rep_cols(denom[:, :1], dv), 0.0).astype(
                o_ref.dtype)
        return carry

    lax.fori_loop(0, nkv, flush, 0)


def _rpa_kernel(tbl_ref, lens_ref, qlens_ref, layer_ref, q_ref, k_hbm,
                v_hbm, o_ref, kbuf, vbuf, sem, slot_ref, m_s, l_s, acc_s,
                **tiles):
    """The ragged-paged-attention kernel (``_rpa_walk``) on dense pools."""
    _rpa_walk(tbl_ref, lens_ref, qlens_ref, layer_ref, None, None, q_ref,
              k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, slot_ref, m_s, l_s,
              acc_s, **tiles)


def _rpa_kernel_quant(tbl_ref, lens_ref, qlens_ref, layer_ref, ksc_ref,
                      vsc_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
                      slot_ref, m_s, l_s, acc_s, **tiles):
    """The same walk over int8 pools: two more scalar-prefetch operands
    carry this layer's per-page dequant scales ([nkv, P] f32, indexed
    through the same block table), applied to the pages a row walks and
    to no other.  One body (``_rpa_walk``); the second name is what a
    profile and the lowered text tell the int8 step by."""
    _rpa_walk(tbl_ref, lens_ref, qlens_ref, layer_ref, ksc_ref, vsc_ref,
              q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, slot_ref, m_s,
              l_s, acc_s, **tiles)


def _rpa_kernel_latent(tbl_ref, lens_ref, qlens_ref, layer_ref, q_ref,
                       c_hbm, o_ref, cbuf, sem, slot_ref, m_s, l_s, acc_s,
                       **tiles):
    """The same walk over ONE pool of latent pages (multi-head latent
    attention in its absorbed form): a token's vector is the key of every
    query head and its first ``o_ref.shape[-1]`` lanes their value.  A
    sibling entry and not a flag of ``_rpa_kernel``, so that a profile and
    the lowered text tell the latent step by name, as ``_rpa_kernel_quant``
    tells the int8 one; the body is the one walk."""
    _rpa_walk(tbl_ref, lens_ref, qlens_ref, layer_ref, None, None, q_ref,
              c_hbm, None, o_ref, cbuf, None, sem, slot_ref, m_s, l_s,
              acc_s, **tiles)


def _layer_operand(layer):
    """The layer as the kernels' ``[1]`` int32 scalar-prefetch operand.  A
    Python int stays concrete (numpy), so the Level-3 verifier can prove
    what reads it; the engine's is its scan's counter."""
    import numpy as np
    if isinstance(layer, int):
        return np.full((1,), layer, np.int32)
    return jnp.reshape(layer, (1,)).astype(jnp.int32)


def _rpa_operands(k_pages, v_pages, k_scales, v_scales, layer):
    """What the kernel and its jnp reference index: the stacked pools
    ``[L, nkv, P, page, d]`` and this layer's scales ``[nkv, P]`` (or
    None).  One layer's 4-D pool is the stack of that one layer
    (``pool[None]``, a bitcast; its scales are already per-layer).  The
    scales of a stack are sliced per layer here — ``[nkv, P]`` is what
    fits SMEM, the whole ``[L, nkv, P]`` does not belong there."""
    if k_pages.ndim == 4:
        return k_pages[None], v_pages[None], k_scales, v_scales
    if k_scales is not None:
        k_scales, v_scales = (
            s[layer] if isinstance(layer, int)
            else lax.dynamic_index_in_dim(s, layer, 0, keepdims=False)
            for s in (k_scales, v_scales))
    return k_pages, v_pages, k_scales, v_scales


def _ragged_attention_jnp(q, k_pages, v_pages, block_tables, seq_lens,
                          q_lens, rep, k_scales=None, v_scales=None,
                          layer=0, window=None, scale=None):
    """Reference implementation and CPU fallback: gather every
    request's pages of layer ``layer`` straight out of the stacked pools
    into a dense [R, Bmax*page] kv span, mask, softmax.  The kernel's
    semantics (same ``_NEG_BIG`` masking, f32 accumulation, exact-zero
    padding rows; with ``window`` the keys ``p - window < j <= p`` only).
    With per-page scales (quantized int8 pools), pages dequant at the
    gather.  Takes 4-D pools like ``_rpa_call``.  ``scale`` (default ``1 /
    sqrt(d)``) and a V pool narrower than K's are the latent pages'
    (``latent_paged_attention``)."""
    k_pages, v_pages, k_scales, v_scales = _rpa_operands(
        k_pages, v_pages, k_scales, v_scales, layer)
    R, nkv, Tr, d = q.shape
    page = k_pages.shape[3]
    Bmax = block_tables.shape[1]
    flat = block_tables.reshape(-1)                  # [R*Bmax]

    def span(pages, scales):
        # (layer, :, flat): the two indices are apart, so the gathered
        # axis leads — [R*Bmax, nkv, page, d] -> [nkv, R, Bmax*page, d]
        seq = pages[layer, :, flat]
        if scales is not None:
            seq = seq.astype(jnp.float32) \
                * jnp.take(scales, flat, axis=1).T[:, :, None, None]
        return seq.reshape(R, Bmax, nkv, page, -1).transpose(
            2, 0, 1, 3, 4).reshape(nkv, R, Bmax * page, -1)

    k_seq, v_seq = span(k_pages, k_scales), span(v_pages, v_scales)
    if scale is None:
        scale = 1.0 / math.sqrt(float(d))
    s = jnp.einsum("rhtd,hrsd->rhts", q.astype(jnp.float32),
                   k_seq.astype(jnp.float32)) * scale
    tok = jnp.arange(Tr, dtype=jnp.int32) // rep     # [Tr]
    qpos = (seq_lens - q_lens)[:, None] + tok[None, :]   # [R, Tr]
    kpos = jnp.arange(Bmax * page, dtype=jnp.int32)  # [S_all]
    mask = ((kpos[None, None, :] <= qpos[:, :, None])
            & (kpos[None, None, :] < seq_lens[:, None, None])
            & (tok[None, :, None] < q_lens[:, None, None]))
    if window is not None:
        mask &= kpos[None, None, :] > qpos[:, :, None] - window
    s = jnp.where(mask[:, None], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rhts,hrsd->rhtd", p, v_seq.astype(jnp.float32))
    valid = tok[None, :] < q_lens[:, None]           # [R, Tr]
    return jnp.where(valid[:, None, :, None], o, 0.0).astype(q.dtype)


def _rpa_call(q, k_pages, v_pages, block_tables, seq_lens, q_lens, *,
              rep, k_scales=None, v_scales=None, layer=0, window=None):
    """Raw pallas_call for the ragged-paged-attention kernel over the
    stacked pools ``[L, nkv, P, page, d]``, which stay in HBM
    (``memory_space=pl.ANY``, no block): the stack is the kernel's
    operand as it stands, ``layer`` rides in as a fourth scalar-prefetch
    operand (``[1]`` int32) and the kernel's own copies read
    ``pool[layer[0], :, tbl[r, j]]``, so nothing slices or copies a
    layer's pool.  One layer's 4-D pool goes the same way as the stack
    of that one layer (decided from the rank).  With
    ``k_scales``/``v_scales`` ([L, nkv, P] f32 per-page dequant scales;
    [nkv, P] beside a 4-D pool) the same walk runs under its int8 name:
    this layer's [nkv, P] scales ride in as two more scalar-prefetch
    operands (SMEM), indexed by the same block table.  ``window`` (static)
    is the walk's: see ``_rpa_walk``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    k_pages, v_pages, k_scales, v_scales = _rpa_operands(
        k_pages, v_pages, k_scales, v_scales, layer)
    R, nkv, Tr, d = q.shape
    page = k_pages.shape[3]
    G = _rpa_group_pages(nkv, Tr, d, page, k_pages.dtype.itemsize,
                         block_tables.shape[1])
    quantized = k_scales is not None
    scalars = (block_tables, seq_lens, q_lens, _layer_operand(layer))
    if quantized:
        scalars += (k_scales, v_scales)

    def row_map(r, *scalars):
        del scalars
        return (r, 0, 0, 0)

    row = pl.BlockSpec((1, nkv, Tr, d), row_map)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    slots = (2, nkv, G * page, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(R,),
        in_specs=[row, in_hbm, in_hbm],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM(slots, k_pages.dtype),           # K page groups
            pltpu.VMEM(slots, v_pages.dtype),           # V page groups
            pltpu.SemaphoreType.DMA((2, 2)),            # [slot, K or V]
            pltpu.SMEM((1,), jnp.int32),                # next row's slot
            pltpu.VMEM((nkv, Tr, _LANES), jnp.float32),  # running max
            pltpu.VMEM((nkv, Tr, _LANES), jnp.float32),  # running sum
            pltpu.VMEM((nkv, Tr, d), jnp.float32),      # accumulator
        ],
    )
    kern = functools.partial(
        _rpa_kernel_quant if quantized else _rpa_kernel,
        page=page, rep=rep, scale=1.0 / math.sqrt(float(d)), window=window)
    call = _pallas_call(
        kern, own_dma=True,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, nkv, Tr, d), q.dtype),
        # a row starts the next row's first copy: rows run in order
        compiler_params=_compiler_params("arbitrary"),
        # no index map carries the page ids any more: the block table
        # (scalar 0) indexes axis 2 of both pools (inputs 1, 2) and the
        # layer (scalar 3) their axis 0 — what the Level-3 verifier bounds
        metadata={"dma_indexes": json.dumps(
            [[0, 1, 2], [0, 2, 2], [3, 1, 0], [3, 2, 0]])},
    )
    return call(*scalars, q, k_pages, v_pages)


def ragged_attention_available(q_shape, kv_shape, dtype=None):
    """True when the Pallas path can serve this problem.  The kernel
    needs lane-aligned pages (page % 128 == 0) — smaller pages are
    served by the jnp reference — plus a TPU backend or interpret
    mode."""
    del q_shape, dtype
    if kv_shape[-2] % _LANES != 0:
        return False
    return _kernels_enabled("ragged_paged_attention")


def ragged_paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                           q_lens, *, rep=1, k_scales=None, v_scales=None,
                           layer=0, window=None):
    """Mixed prefill+decode attention over a paged KV cache.

    q            [R, nkv, Tc*rep, d] per-request q slots (GQA: the rep
                 q heads of kv head h sit at rows tok*rep..tok*rep+rep-1)
    k/v pages    [L, nkv, P, page, d] the stacked pools of every layer,
                 as the engine holds them: passed whole and never
                 sliced — the kernel copies a row's live pages of
                 ``layer`` out of them
    layer        which layer of the stack to attend over (an int or a
                 traced int32 scalar, e.g. the counter of a layer scan)
    block_tables [R, Bmax] i32, seq_lens/q_lens [R] i32 (see module
                 section comment for the ragged-batch contract)
    k/v_scales   optional [L, nkv, P] f32 per-page dequant scales for
                 quantized (int8) pools; this layer's [nkv, P] are
                 sliced out (12 KB) and ride into the kernel as two
                 extra scalar-prefetch operands, pages dequant on read
    window       static: None, or W for sliding-window attention, a q row
                 at position p seeing the keys ``p - W < j <= p``.  A
                 row's walk then starts at the page of the first key it
                 sees, so the table's earlier columns are never read and
                 may map a ring of pages (``models/phi4flash.py``)

    One layer's pool ``[nkv, P, page, d]`` (with ``[nkv, P]`` scales)
    takes the same path as the stack of that one layer, layer 0.

    Decode is the Tc == 1 specialization of the same kernel; its tile
    parameters follow from the shapes.  The jnp reference serves off-TPU
    and lane-unaligned pages (a choice made from platform and shape); a
    kernel that fails to lower or compile raises."""
    if not ragged_attention_available(q.shape, k_pages.shape, q.dtype):
        return _ragged_attention_jnp(q, k_pages, v_pages, block_tables,
                                     seq_lens, q_lens, rep,
                                     k_scales, v_scales, layer, window)
    return _rpa_call(q, k_pages, v_pages, block_tables, seq_lens,
                     q_lens, rep=rep, k_scales=k_scales, v_scales=v_scales,
                     layer=layer, window=window)


# ---------------------------------------------------------------------------
# Latent pages: one vector a token that is key and value both
# ---------------------------------------------------------------------------
#
# Multi-head latent attention in its absorbed form keeps, a token and layer,
# one vector ``[c | k_pe | 0]`` (``models/deepseek_v2.py``): every query head
# scores against the whole of it and takes the weighted sum of its first
# ``v_lanes`` lanes.  Read as a K pool and a V pool the page would cross HBM
# twice, which is the traffic the latent exists to save; so the walk copies a
# page once and slices V out of the K tile in VMEM (``_rpa_walk`` with no V
# pool).  Everything else is the walk's: one K/V "head", ``rep`` query heads
# a token, the caller's ``scale``.

def _rpa_latent_call(q, pages, block_tables, seq_lens, q_lens, *, rep,
                     v_lanes, scale, layer=0):
    """Raw pallas_call of ``_rpa_kernel_latent`` over the stacked latent
    pool ``[L, 1, P, page, d]``, left in HBM like ``_rpa_call``'s pools."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    if pages.ndim == 4:
        pages = pages[None]
    R, nkv, Tr, d = q.shape
    page = pages.shape[3]
    # a row of more query rows than a block (64 heads x 16 tokens: 1024)
    # walks them a block at a time, under a limit sized to what the row's
    # own blocks take whatever the walk does, as much again for the walk
    qblk = _RPA_Q_BLOCK if Tr > _RPA_Q_BLOCK and Tr % _RPA_Q_BLOCK == 0 \
        else None
    budget = None if qblk is None else max(
        _VMEM_BUDGET, 2 * _rpa_row_bytes(nkv, Tr, d, pages.dtype.itemsize))
    G = _rpa_group_pages(nkv, Tr, d, page, pages.dtype.itemsize,
                         block_tables.shape[1], qblk, budget)
    scalars = (block_tables, seq_lens, q_lens, _layer_operand(layer))

    def row_map(r, *scalars):
        del scalars
        return (r, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(R,),
        in_specs=[pl.BlockSpec((1, nkv, Tr, d), row_map),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, nkv, Tr, v_lanes), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, nkv, G * page, d), pages.dtype),  # page groups
            pltpu.SemaphoreType.DMA((2, 1)),             # [slot, the pool]
            pltpu.SMEM((1,), jnp.int32),                # next row's slot
            pltpu.VMEM((nkv, Tr, _LANES), jnp.float32),  # running max
            pltpu.VMEM((nkv, Tr, _LANES), jnp.float32),  # running sum
            pltpu.VMEM((nkv, Tr, v_lanes), jnp.float32),  # accumulator
        ],
    )
    call = _pallas_call(
        functools.partial(_rpa_kernel_latent, page=page, rep=rep,
                          scale=float(scale), qblk=qblk),
        own_dma=True,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, nkv, Tr, v_lanes), q.dtype),
        compiler_params=_compiler_params("arbitrary") if budget is None
        else pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                  vmem_limit_bytes=budget + budget // 2),
        # the block table (scalar 0) indexes axis 2 of the pool (input 1)
        # and the layer (scalar 3) its axis 0
        metadata={"dma_indexes": json.dumps([[0, 1, 2], [3, 1, 0]])},
    )
    return call(*scalars, q, pages)


def latent_paged_attention(q, pages, block_tables, seq_lens, q_lens, *, rep,
                           v_lanes, scale, layer=0):
    """Mixed prefill+decode attention over paged LATENT vectors.

    q            [R, 1, Tc*rep, d] the ``rep`` query heads of a token at
                 rows tok*rep..tok*rep+rep-1, each as wide as a latent
    pages        [L, 1, P, page, d] the stacked pool (or one layer's 4-D
                 pool): a token's vector is its key, and its first
                 ``v_lanes`` lanes (a multiple of 128) its value
    scale        what the scores are multiplied by (the model's; nothing
                 follows from ``d`` here)

    Returns ``[R, 1, Tc*rep, v_lanes]``.  The ragged-batch contract, the
    page walk and the choice between the Mosaic kernel and the jnp body are
    ``ragged_paged_attention``'s."""
    if not ragged_attention_available(q.shape, pages.shape, q.dtype) \
            or v_lanes % _LANES or pages.shape[-1] % _LANES:
        return _ragged_attention_jnp(
            q, pages, pages[..., :v_lanes], block_tables, seq_lens, q_lens,
            rep, layer=layer, scale=scale)
    return _rpa_latent_call(q, pages, block_tables, seq_lens, q_lens,
                            rep=rep, v_lanes=v_lanes, scale=scale,
                            layer=layer)


# ---------------------------------------------------------------------------
# Paged KV write: a step's new tokens into the stacked pools, in place
# ---------------------------------------------------------------------------
#
# The other half of keeping the pools one buffer: ``forward_paged`` hands
# the stacked pools [L, nkv, P, page, d] and each request's chunk of new
# k/v to ``paged_kv_write``, which writes token t < q_lens[r] of request r
# at kv position seq_lens[r] - q_lens[r] + t of layer ``layer`` through the
# block table, and returns the same buffers (``input_output_aliases``).
# An XLA scatter cannot do this on the TPU without harm: with a (nkv, d)
# window it wants the head axis beside d and relayouts the whole stack
# around itself, and as single rows (6,144 of 256 B a layer and pool) it
# costs 10 ms a step and pool (PERF.md section 6, PR 26).  So the write is a
# Mosaic kernel, and the Mosaic layout is the only layout the pools have.
#
# A row of a packed dtype cannot be stored alone, so the unit is the
# ``rows``-row tile that holds it (8 rows of 4 bytes: 16 of bf16): grid
# (R, n_t) where n_t tiles cover any chunk of Tc tokens; grid point
# (r, i) reads the tile of all nkv heads, replaces the rows this chunk
# owns, and writes it back.  Tiles a chunk does not reach (and inactive
# rows) map to tile 0 of the allocator's null page and write it back as
# read.  Two requests never write one tile: a page under write has one
# owner (shared prefix pages are full, copy-on-write forks before a write).

def _kv_tile_rows(dtype):
    """Rows of the sublane tile of ``dtype``: 8 of four bytes, packed."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _kv_write_kernel(layer_ref, pg_ref, sub_ref, shift_ref, qlens_ref,
                     *refs, rows, Tc):
    """Grid point (r, i): the i-th ``rows``-row tile that request r's
    chunk touches, all kv heads, of layer ``layer_ref[0]``.  Row j of the
    tile takes chunk token ``j - shift`` where that is a real token.
    ``refs`` are the new rows of every pool, then the pools in, then the
    pools out (K and V; one pool of latent vectors)."""
    from jax.experimental import pallas as pl
    del layer_ref, pg_ref, sub_ref
    n = len(refs) // 3
    r = pl.program_id(0)
    i = pl.program_id(1)
    qlen = qlens_ref[r]
    kin_ref = refs[n]
    shape = kin_ref.shape[1:2] + kin_ref.shape[3:]       # (nkv, rows, d)
    tok = lax.broadcasted_iota(jnp.int32, shape, 1) - shift_ref[r, i]
    for new_ref, in_ref, out_ref in zip(refs[:n], refs[n:2 * n],
                                        refs[2 * n:]):
        tile = in_ref[0, :, 0].astype(jnp.float32)       # [nkv, rows, d]
        new = new_ref[0].astype(jnp.float32)             # [nkv, Tc, d]
        for t in range(Tc):
            tile = jnp.where((tok == t) & (t < qlen),
                             new[:, t:t + 1, :], tile)
        out_ref[0, :, 0] = tile.astype(out_ref.dtype)


def _kv_write_tiles(block_tables, seq_lens, q_lens, Tc, page, rows):
    """Which tile each grid point (r, i) of the write kernel holds:
    (pool page, tile within the page, start - first position of the tile),
    each [R, n_t] int32.  Tiles past the chunk's end and inactive rows
    hold tile 0 of the null page."""
    n_t = (Tc + rows - 2) // rows + 1
    Bmax = block_tables.shape[1]
    start = (seq_lens - q_lens).astype(jnp.int32)
    pos0 = (start[:, None] // rows
            + jnp.arange(n_t, dtype=jnp.int32)[None, :]) * rows
    reached = (q_lens > 0)[:, None] & (pos0 < seq_lens[:, None])
    pg = jnp.take_along_axis(
        block_tables, jnp.clip(pos0 // page, 0, Bmax - 1), axis=1)
    return (jnp.where(reached, pg, 0).astype(jnp.int32),
            jnp.where(reached, pos0 % page // rows, 0).astype(jnp.int32),
            start[:, None] - pos0)


def _pools_write_call(pools, news, block_tables, seq_lens, q_lens, layer):
    """Raw pallas_call of the paged write: every pool of ``pools`` aliased
    in to out, each taking its own new rows ``news[i] [R, Tc, nkv, d]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    R, Tc, nkv, d = news[0].shape
    n = len(pools)
    page = pools[0].shape[3]
    rows = _kv_tile_rows(pools[0].dtype)
    pg, sub, shift = _kv_write_tiles(block_tables, seq_lens, q_lens, Tc,
                                     page, rows)
    scalars = (_layer_operand(layer), pg, sub, shift, q_lens)

    def new_map(r, i, *scalars):
        del i, scalars
        return (r, 0, 0, 0)

    def pool_map(r, i, layer, pg, sub, *rest):
        del rest
        return (layer[0], 0, pg[r, i], sub[r, i], 0)

    new_spec = pl.BlockSpec((1, nkv, Tc, d), new_map)
    pool_spec = pl.BlockSpec((1, nkv, 1, rows, d), pool_map)
    call = _pallas_call(
        functools.partial(_kv_write_kernel, rows=rows, Tc=Tc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(R, pg.shape[1]),
            in_specs=[new_spec] * n + [pool_spec] * n,
            out_specs=[pool_spec] * n),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # operand numbers count the scalars: K and V's pools are 7 and 8
        input_output_aliases={len(scalars) + n + i: i for i in range(n)},
        compiler_params=_compiler_params("arbitrary", "arbitrary"),
    )
    # [R, Tc, nkv, d] -> [R, nkv, Tc, d]: a head's chunk rows together
    return tuple(call(*scalars,
                      *(new.transpose(0, 2, 1, 3).astype(p.dtype)
                        for new, p in zip(news, pools)),
                      *pools))


def _pools_write_jnp(pools, news, block_tables, seq_lens, q_lens, layer):
    """Reference and off-TPU body of the paged write: an XLA scatter of
    single rows at (layer, head, page, row).  Rows, not (nkv, d) windows:
    every index leads, so the scatter works on the stack's own layout."""
    R, Tc, nkv, d = news[0].shape
    num_pages, page = pools[0].shape[2], pools[0].shape[3]
    t_off = jnp.arange(Tc, dtype=jnp.int32)[None, :]
    qpos = (seq_lens - q_lens).astype(jnp.int32)[:, None] + t_off
    blk = jnp.clip(qpos // page, 0, block_tables.shape[1] - 1)
    # padding tokens (t >= q_len) get a page past the pool and drop
    pg = jnp.where(t_off < q_lens[:, None],
                   jnp.take_along_axis(block_tables, blk, axis=1),
                   num_pages).reshape(-1, 1)
    off = (qpos % page).reshape(-1, 1)
    heads = jnp.arange(nkv, dtype=jnp.int32)[None, :]
    return tuple(
        p.at[layer, heads, pg, off].set(
            new.reshape(R * Tc, nkv, d).astype(p.dtype), mode="drop")
        for p, new in zip(pools, news))


def kv_write_available(kv_shape, dtype):
    """True when the Pallas write kernel can serve these pools: whole
    sublane tiles in a page, lane-aligned heads, a TPU backend or
    interpret mode (the same choice as ``ragged_attention_available``)."""
    page, d = kv_shape[-2], kv_shape[-1]
    if page % _kv_tile_rows(dtype) or d % _LANES:
        return False
    return _kernels_enabled("paged_kv_write")


def paged_kv_write(k_pages, v_pages, k_new, v_new, block_tables, seq_lens,
                   q_lens, *, layer=0):
    """Write a step's new k/v into the stacked page pools, in place.

    k/v pages    [L, nkv, P, page, d] the stacked pools, as the engine
                 holds them; returned as the same buffers, updated
    k/v new      [R, Tc, nkv, d] each request's chunk; tokens
                 t < q_lens[r] go to kv positions seq_lens[r] -
                 q_lens[r] + t, the rest is padding and is not written
    layer        which layer of the stack (an int or a traced scalar)
    block_tables [R, Bmax] i32, seq_lens/q_lens [R] i32 as for
                 ``ragged_paged_attention``

    Only the new tokens' tiles move: no operation yields a pool or a
    layer's pool.  On the TPU a Mosaic kernel (``_kv_write_kernel``);
    off-TPU and for pages or heads that are not tile-aligned an XLA
    scatter of rows."""
    write = (_pools_write_call
             if kv_write_available(k_pages.shape, k_pages.dtype)
             else _pools_write_jnp)
    return write((k_pages, v_pages), (k_new, v_new), block_tables, seq_lens,
                 q_lens, layer)


def paged_latent_write(pages, new, block_tables, seq_lens, q_lens, *,
                       layer=0):
    """``paged_kv_write`` for ONE pool: a step's new latent vectors ``new
    [R, Tc, 1, d]`` into the stacked pool ``pages [L, 1, P, page, d]``, in
    place, by the same kernel on one pool."""
    write = (_pools_write_call
             if kv_write_available(pages.shape, pages.dtype)
             else _pools_write_jnp)
    return write((pages,), (new,), block_tables, seq_lens, q_lens, layer)[0]


# ---------------------------------------------------------------------------
# Selective scan of a Mamba layer in the serve step, its state in place
# ---------------------------------------------------------------------------
#
# ``S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t``, ``y_t = S_t C_t`` over the
# live positions of every row's chunk, on layer ``layer`` of the stacked
# state ``ssm [M, N, R, E]`` float32, which goes in whole and comes back as
# the same buffer (``input_output_aliases``; the layer rides in by scalar
# prefetch, as for the K/V pools).  Grid ``(E / Et, R / 8)``: a grid point
# holds the ``[N, 8, Et]`` tile of 8 rows' state in VMEM, read once and
# written once whatever the chunk's length, and walks ``t`` from 0 to the
# largest ``q_lens`` of its 8 rows: one position for a group of decode rows,
# none for an idle group, whose tile comes back bit for bit.  A row with
# fewer live positions than its group keeps its state past its own
# ``q_len``: its ``dt``, ``dt x`` and ``B`` are zero there by select, so
# whatever the dead positions of the inputs hold never reaches a state.
#
# The per-position inputs ``dt, x [T, E]``, ``B, C [T, N]`` are the step's
# FLAT tokens, row after row (``models/step_layout.py``): position t of row r
# is flat token ``start[r] + t``.  No padded ``[R, Tc, E]`` array exists
# (PR 38).  A lane tile's ``[T, Et]`` blocks of ``dt`` and ``x`` stay in VMEM
# for all its groups (the lane tiles are the outer grid axis, so the
# pipeline fetches them once a tile), and a position's 8 rows are picked out
# of them one live row at a time, by the row's offset from SMEM, into an
# ``[8, Et]`` slab; ``y`` goes back the same way, a live row at a time into
# the tile's resident ``[T, Et]`` block, which is written to HBM once.  So a
# step reads and writes ``T x E`` of each, and ``y`` at a flat position that
# holds no fed token is whatever VMEM held: the caller selects it away.

_SSM_ROWS = 8      # rows of a group: the float32 sublane tile of the R axis


def scan_positions(q_lens, rows=_SSM_ROWS) -> int:
    """The row-positions ``_ssm_scan_kernel`` walks in one layer for the
    host's ``q_lens [R]``: every group of ``rows`` rows walks the largest
    ``q_len`` among them.  ``R x Tc`` is what a padded scan walks."""
    import numpy as np
    q = np.asarray(q_lens, np.int64)
    q = np.pad(q, (0, -len(q) % rows)).reshape(-1, rows)
    return int(rows * q.max(axis=1).sum())


def _ssm_scan_tile(N, E, T):
    """``Et``: the widest tile of E, a multiple of the lanes that divides
    E, whose working set fits ``_VMEM_BUDGET`` (None: no tile does).  A
    lane of the tile costs the state's block in and out, A's block and the
    ``[T, Et]`` blocks of dt, x and y, two buffers each, and the three
    ``[8, Et]`` slabs; the ``[T, N]`` blocks of B and C pad N to the lanes,
    and a position's columns of them take ``[N, 8, 128]`` each."""
    per_lane = 4 * (4 * N * _SSM_ROWS + 2 * N + 6 * T + 3 * _SSM_ROWS)
    fixed = 4 * (4 * T + 2 * N * _SSM_ROWS + 2 * _SSM_ROWS) * _LANES
    for k in range(1, E // _LANES + 1):
        Et = E // k
        if E % k == 0 and Et % _LANES == 0 \
                and fixed + per_lane * Et <= _VMEM_BUDGET:
            return Et
    return None


def _ssm_scan_kernel(layer_ref, qlens_ref, start_ref, fresh_ref, dt_ref,
                     x_ref, b_ref, c_ref, a_ref, s_in, s_out, y_ref, dtbuf,
                     xbuf, ybuf, bbuf, cbuf, bp_ref, cp_ref, *, Tc):
    """Grid point (e, g): rows ``8g .. 8g+7``, lanes ``e Et .. (e+1) Et`` of
    the state of layer ``layer_ref[0]``.  ``dt_ref``, ``x_ref``, ``y_ref``
    are the lane tile's ``[T, Et]`` blocks of the flat tokens, ``b_ref``,
    ``c_ref`` the whole ``[T, N]``; ``Tc`` bounds a row's chunk."""
    from jax.experimental import pallas as pl
    del layer_ref
    T = dt_ref.shape[0]
    G8 = _SSM_ROWS
    g = pl.program_id(1)
    N, Et = a_ref.shape

    # the 8 rows' lengths down the sublanes of a vreg, from SMEM
    sub = lax.broadcasted_iota(jnp.int32, (G8, _LANES), 0)
    qv = jnp.zeros((G8, _LANES), jnp.int32)
    fv = jnp.zeros((G8, _LANES), jnp.int32)
    maxq = jnp.int32(0)
    qs, starts = [], []
    for j in range(G8):
        q = jnp.minimum(qlens_ref[g * G8 + j], Tc)
        maxq = jnp.maximum(maxq, q)
        qv = jnp.where(sub == j, q, qv)
        fv = jnp.where(sub == j, fresh_ref[g * G8 + j], fv)
        qs.append(q)
        starts.append(start_ref[g * G8 + j])

    def live_rows(t, body):
        """``body(j, i)`` for every row j of the group that lives at
        position t, i its flat token."""
        for j in range(G8):
            @pl.when(t < qs[j])
            def _row(j=j):
                body(j, jnp.minimum(starts[j] + t, T - 1))

    @pl.when(maxq == 0)
    def _idle_group():
        s_out[...] = s_in[...]

    @pl.when(maxq > 0)
    def _live_group():
        keep = jnp.tile(fv, (1, Et // _LANES)) == 0
        s_out[0] = jnp.where(keep[None], s_in[0], 0.0)
        onehot = (lax.broadcasted_iota(jnp.int32, (N, G8, N), 0)
                  == lax.broadcasted_iota(jnp.int32, (N, G8, N), 2))

        def planes(m):
            """``m [8, N]`` as ``[N, 8, 128]``: column n along the lanes of
            plane n."""
            col = jnp.sum(jnp.where(onehot, m[None], 0.0), axis=2,
                          keepdims=True)
            return jnp.broadcast_to(col, (N, G8, _LANES))

        def pick(j, i):
            dtbuf[pl.ds(j, 1)] = dt_ref[pl.ds(i, 1)]
            xbuf[pl.ds(j, 1)] = x_ref[pl.ds(i, 1)]
            bbuf[pl.ds(j, 1)] = b_ref[pl.ds(i, 1)]
            cbuf[pl.ds(j, 1)] = c_ref[pl.ds(i, 1)]

        def put(j, i):
            y_ref[pl.ds(i, 1)] = ybuf[pl.ds(j, 1)]

        def position(t, carry):
            live_rows(t, pick)
            live = t < qv                                    # [8, 128]
            livee = jnp.tile(live, (1, Et // _LANES))
            # a dead row's slab rows are stale or never written: its dt,
            # dt x and B are zero by select
            dt = jnp.where(livee, dtbuf[...], 0.0)
            dx = jnp.where(livee, dt * xbuf[...], 0.0)
            bp_ref[...] = planes(jnp.where(live[:, :N], bbuf[...], 0.0))
            cp_ref[...] = planes(cbuf[...])

            def plane(n, y):
                s = (jnp.exp(dt * a_ref[pl.ds(n, 1)]) * s_out[0, n]
                     + dx * jnp.tile(bp_ref[n], (1, Et // _LANES)))
                s_out[0, n] = s
                return y + s * jnp.tile(cp_ref[n], (1, Et // _LANES))

            # traced once and unrolled in the lowering, where n is a constant
            # again: a rolled loop's dynamically indexed stores order every
            # load after them (twice the time), and the N planes written out
            # in Python are four hundred equations that every engine start
            # traces (PERF.md section 6, PR 32).  Whole [8, Et] rows an
            # operation: Mosaic unrolls them over the lane tiles itself
            ybuf[...] = lax.fori_loop(
                0, N, plane, jnp.zeros((G8, Et), jnp.float32), unroll=True)
            live_rows(t, put)
            return carry

        lax.fori_loop(0, maxq, position, 0)


def _ssm_scan_jnp(ssm, dt, x, Bm, Cm, A, q_lens, start, fresh, layer, Tc):
    """Reference and off-TPU body of ``selective_scan``, on the same flat
    arguments (it pads them to rows itself): the layer's state out of its
    stack, the reset, the recurrence unrolled over the chunk's positions as
    plain array expressions, no loop carry, so that XLA may fuse several
    positions into one pass over the state (on a v5e a chunk of 16 costs
    1.9 ms a layer alone against 2.3 ms as a ``lax.scan`` over positions:
    PERF.md section 6, PR 27), and the write-back.  A position ``t >=
    q_lens[r]`` has ``dt``, ``dt x`` and ``B`` zero by select and so leaves
    the state as it was, whatever the inputs hold there; ``y`` is zero at
    every flat position that holds no fed token."""
    T = dt.shape[0]
    q_lens = jnp.minimum(q_lens.astype(jnp.int32), Tc)
    start = start.astype(jnp.int32)
    # padded rows [R, Tc, ..]: position t of row r is flat start[r] + t; a
    # dead position holds some other token's values, selected away below
    at = jnp.minimum(start[:, None] + jnp.arange(Tc, dtype=jnp.int32), T - 1)
    dt, x, Bm, Cm = (jnp.take(a, at, axis=0) for a in (dt, x, Bm, Cm))
    s = jnp.where(fresh[None, :, None], 0,
                  lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False))
    real = (jnp.arange(Tc)[None, :] < q_lens[:, None])[:, :, None]
    dt = jnp.where(real, dt, 0.0)
    dx = jnp.where(real, dt * x, 0.0)
    Bm = jnp.where(real, Bm, 0.0)
    ys = []
    for t in range(Tc):
        s = (jnp.exp(dt[None, :, t] * A[:, None, :]) * s
             + dx[None, :, t] * Bm[:, t].T[:, :, None])
        ys.append(jnp.sum(s * Cm[:, t].T[:, :, None], axis=0))
    dst = jnp.where(real[:, :, 0], at, T)
    y = jnp.zeros((T, x.shape[-1]), jnp.float32).at[dst].set(
        jnp.stack(ys, 1), mode="drop")
    return y, lax.dynamic_update_index_in_dim(
        ssm, s.astype(ssm.dtype), layer, 0)


def _ssm_scan_call(ssm, dt, x, Bm, Cm, A, q_lens, start, fresh, layer, Tc,
                   Et=None):
    """Raw pallas_call of the selective scan: the state stack aliased in to
    out, the step's flat tokens, tiles of ``Et`` lanes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    M, N, R, E = ssm.shape
    T = dt.shape[0]
    Et = Et or _ssm_scan_tile(N, E, T)
    f32 = jnp.float32
    scalars = (_layer_operand(layer), q_lens.astype(jnp.int32),
               start.astype(jnp.int32), fresh.astype(jnp.int32))

    def state_map(e, g, layer, *rest):
        del rest
        return (layer[0], 0, g, e)

    tokens = pl.BlockSpec((T, Et), lambda e, g, *s: (0, e))
    cols = pl.BlockSpec((T, N), lambda e, g, *s: (0, 0))
    a_spec = pl.BlockSpec((N, Et), lambda e, g, *s: (0, e))
    state = pl.BlockSpec((1, N, _SSM_ROWS, Et), state_map)
    slab = pltpu.VMEM((_SSM_ROWS, Et), f32)
    col = pltpu.VMEM((_SSM_ROWS, N), f32)
    planes = pltpu.VMEM((N, _SSM_ROWS, _LANES), f32)     # B_t's, C_t's columns
    operands = tuple(a.astype(f32) for a in (dt, x, Bm, Cm, A)) + (ssm,)
    call = _pallas_call(
        functools.partial(_ssm_scan_kernel, Tc=Tc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(E // Et, R // _SSM_ROWS),
            in_specs=[tokens, tokens, cols, cols, a_spec, state],
            out_specs=[state, tokens],
            scratch_shapes=[slab, slab, slab, col, col, planes, planes]),
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((T, E), f32)],
        # operand numbers count the scalars: the state stack is the last
        input_output_aliases={len(scalars) + len(operands) - 1: 0},
        # a lane tile's y block is written by every group of its rows
        compiler_params=_compiler_params("parallel", "arbitrary"),
    )
    ssm, y = call(*scalars, *operands)
    return y, ssm


# A model calls the scan from every run of Mamba layers it scans over (three
# in Jamba's step): as one jitted function the kernel is traced and lowered
# once a program, which every engine start pays for, compile cache or not
# (PERF.md section 6, PR 32).  The tile is static: it follows
# ``_VMEM_BUDGET``, which the trace cache does not see.
_ssm_scan_jit = jax.jit(_ssm_scan_call, static_argnames=("Tc", "Et"))


def ssm_scan_available(ssm_shape, dtype, T):
    """True when the Pallas scan can serve this state stack ``[M, N, R,
    E]`` on ``T`` flat tokens: float32, whole groups of 8 rows, lane-aligned
    E, whole sublane tiles of tokens, a tile within ``_VMEM_BUDGET``, and a
    TPU backend or interpret mode."""
    N, R, E = ssm_shape[1:]
    if jnp.dtype(dtype) != jnp.float32 or R % _SSM_ROWS or E % _LANES \
            or T % _SSM_ROWS or _ssm_scan_tile(N, E, T) is None:
        return False
    return _kernels_enabled("selective_scan")


def selective_scan(ssm, dt, x, Bm, Cm, A, q_lens, start, fresh, *, Tc,
                   layer=0):
    """The selective scan of one Mamba layer over a step's ragged chunks,
    its state updated in place in the stack, on the step's flat tokens.

    ssm          [M, N, R, E] float32, the recurrent state of every Mamba
                 layer and engine slot; returned as the same buffer with
                 layer ``layer`` advanced
    dt, x        [T, E] the step sizes (after softplus) and the
                 convolution's activations of the step's tokens, row after
                 row (``StepLayout``)
    Bm, Cm       [T, N]; A [N, E] (``-exp(A_log)``)
    q_lens       [R] i32: row r has ``q_lens[r]`` live positions, at most
                 ``Tc`` (static)
    start        [R] i32: position t of row r is flat token ``start[r] +
                 t``; the caller guarantees ``start[r] + q_lens[r] <= T``
                 (``cumsum(q_lens) - q_lens`` for the compact batch, ``r x
                 Tc`` for padded rows laid end to end).  What the flat
                 arrays hold anywhere else advances no state (NaN included)
    fresh        [R] bool: the row's chunk starts a request, its state
                 starts from zero
    layer        which layer of the stack (an int or a traced scalar)

    Returns ``(y [T, E] float32, ssm)`` with ``S_t = exp(dt_t A) S_{t-1} +
    (dt_t x_t) B_t`` and ``y_t = S_t C_t`` at the fed tokens, all in
    float32; ``y`` at a flat position that holds no fed token is
    unspecified (the kernel writes the fed tokens alone): select it away.
    On the TPU a Mosaic kernel (``_ssm_scan_kernel``) for both programs,
    ``Tc = chunk`` and ``Tc = 1``; off-TPU, for a row or token count that is
    no multiple of 8 and for an E that is no multiple of 128, the XLA
    body."""
    if not ssm_scan_available(ssm.shape, ssm.dtype, dt.shape[0]):
        return _ssm_scan_jnp(ssm, dt, x, Bm, Cm, A, q_lens, start, fresh,
                             layer, Tc)
    # (the layer as an int32 array: a Python int would be another trace)
    return _ssm_scan_jit(
        ssm, dt, x, Bm, Cm, A, q_lens, start, fresh,
        jnp.asarray(layer, jnp.int32), Tc=Tc,
        Et=_ssm_scan_tile(ssm.shape[1], ssm.shape[3], dt.shape[0]))


# ---------------------------------------------------------------------------
# Routed experts: one grouped SwiGLU over rows sorted by expert
# ---------------------------------------------------------------------------
#
# ``y[i] = W_d[e] (silu(W_g[e] x[i]) * W_u[e] x[i])`` for the rows ``x [M, D]``
# of a step's (token, expert) pairs sorted by expert: expert e owns the
# ``group_sizes[e]`` rows after those of the experts before it, and rows past
# the last group belong to none (dropped pairs, padding).  No capacity and no
# dispatch tensor: an expert costs its weights once and a group of no rows
# costs nothing.
#
# Grid ``(V,)`` of VISITS, one per (expert, 128-row tile) pair that share a
# row, in order; ``V = M / 128 + E - 1`` bounds them whatever the routing
# (the groups and the tiles are two partitions of one row axis), and visits
# past the live ones hold the last live visit's blocks and do nothing.  Which
# expert and tile a visit holds rides in by scalar prefetch, as the block
# table rides into ``_rpa_kernel``; consecutive visits of one expert keep its
# weights in VMEM, consecutive visits of one tile its output, so a step reads
# every hit expert's three matrices once (17.3 MB at D 2048, F 1408: 21 us at
# the v5e's 819 GB/s, over the three products' MXU time at 128 rows) and the
# rows once a visit.  The weights stay the stacks of every layer ``[L, E, ..]``
# with the layer as a scalar: nothing slices 1.1 GB out of them.
#
# An expert too wide for VMEM (D 6144, F 2048: 151 MB for two slots of its
# three matrices) is visited a BLOCK of its width at a time, grid ``(V, F /
# f)``: a turn holds ``(D, f)`` of the gate and up matrices and ``(f, D)`` of
# the down matrix, and the down products of a visit's blocks add up in a
# float32 scratch that the visit's last block folds into the tile.  ``f`` is
# the largest divisor of F in whole 128-lane tiles whose working set fits
# ``_MOE_BUDGET`` (``_moe_width_block``; 1408 at D 2048 stays one block and
# the one-block kernel is the one above, untouched).  With several blocks an
# expert's weights are read once per 128-row tile that visits it, not once a
# step: consecutive visits of one expert no longer share a block.

_MOE_ROWS = 128    # rows of a visit's tile
_MOE_VMEM = 100 * 2 ** 20   # two slots of an expert's three matrices, tiles
_MOE_BUDGET = 90 * 2 ** 20  # what a turn's blocks and tiles may take of it


def _moe_width_block(D, F, itemsize, tm=_MOE_ROWS):
    """``f``, the lanes of an expert's width one turn holds: the largest
    divisor of F in whole 128-lane tiles whose working set fits
    ``_MOE_BUDGET`` (F itself where the whole expert does; None where no
    block does).  A lane of the block costs both slots of the three
    matrices' ``D`` elements and three float32 columns of a tile (gate, up
    and their product); a turn, whatever f, the tile of rows in both slots,
    the float32 output in both and the accumulator."""
    fixed = tm * D * (2 * itemsize + 2 * 4 + 4)
    per_lane = 2 * 3 * D * itemsize + 3 * tm * 4
    for n in range(1, F // _LANES + 1):
        f = F // n
        if F % n == 0 and f % _LANES == 0 \
                and fixed + per_lane * f <= _MOE_BUDGET:
            return f
    return None


def _expert_visits(group_sizes, M, tm=_MOE_ROWS):
    """Which expert and which ``tm``-row tile each of the ``V = M / tm + E -
    1`` visits holds (``[V]`` int32 each; visits past the live ones repeat
    the last live one), the groups' first rows and ends ``[E]``, and the
    number of live visits ``[1]``."""
    E = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    vend = jnp.cumsum(tiles)
    n = vend[-1]
    v = jnp.minimum(jnp.arange(M // tm + E - 1, dtype=jnp.int32),
                    jnp.maximum(n - 1, 0))
    g = jnp.minimum(jnp.sum(v[:, None] >= vend[None, :], axis=1), E - 1)
    tile = jnp.clip(first[g] + v - (vend - tiles)[g], 0, M // tm - 1)
    return (g.astype(jnp.int32), tile.astype(jnp.int32), starts, ends,
            n.reshape(1))


def _moe_experts_kernel(layer_ref, g_ref, tile_ref, start_ref, end_ref,
                        n_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, *, tm):
    """Visit v: the rows of tile ``tile_ref[v]`` through expert
    ``g_ref[v]``'s SwiGLU, kept where the row is the expert's own.  The
    first visit of a tile zeroes what no expert owns."""
    from jax.experimental import pallas as pl
    del layer_ref
    v = pl.program_id(0)

    @pl.when(v < n_ref[0])
    def _live_visit():
        g, tile = g_ref[v], tile_ref[v]
        x = x_ref[...]                                       # [tm, D]
        a = lax.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
        b = lax.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
        h = (a * jax.nn.sigmoid(a) * b).astype(x.dtype)      # [tm, F]
        y = lax.dot(h, wd_ref[0, 0], preferred_element_type=jnp.float32)
        row = tile * tm + lax.broadcasted_iota(jnp.int32, y.shape, 0)
        mine = (row >= start_ref[g]) & (row < end_ref[g])
        fresh = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != tile)
        kept = jnp.where(fresh, 0.0, o_ref[...])
        o_ref[...] = jnp.where(mine, y, kept).astype(o_ref.dtype)


def _moe_experts_kernel_blocked(layer_ref, g_ref, tile_ref, start_ref,
                                end_ref, n_ref, x_ref, wg_ref, wu_ref, wd_ref,
                                o_ref, acc_ref, *, tm):
    """Turn (v, j): block j of expert ``g_ref[v]``'s width on the rows of
    tile ``tile_ref[v]``.  The down products of a visit's blocks add up in
    ``acc_ref``; the last block keeps the sum where the row is the expert's
    own, as ``_moe_experts_kernel`` keeps its one product."""
    from jax.experimental import pallas as pl
    del layer_ref
    v, j = pl.program_id(0), pl.program_id(1)

    @pl.when(v < n_ref[0])
    def _live_visit():
        g, tile = g_ref[v], tile_ref[v]
        x = x_ref[...]                                       # [tm, D]
        a = lax.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
        b = lax.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
        h = (a * jax.nn.sigmoid(a) * b).astype(x.dtype)      # [tm, f]
        y = lax.dot(h, wd_ref[0, 0], preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _first_block():
            acc_ref[...] = y

        @pl.when(j > 0)
        def _later_block():
            acc_ref[...] += y

        @pl.when(j == pl.num_programs(1) - 1)
        def _last_block():
            row = tile * tm + lax.broadcasted_iota(jnp.int32, y.shape, 0)
            mine = (row >= start_ref[g]) & (row < end_ref[g])
            fresh = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != tile)
            kept = jnp.where(fresh, 0.0, o_ref[...])
            o_ref[...] = jnp.where(mine, acc_ref[...], kept).astype(
                o_ref.dtype)


def _expert_stacks(w_gate, w_up, w_down):
    """One layer's ``[E, ..]`` matrices as the stack of that one layer."""
    if w_gate.ndim == 3:
        return w_gate[None], w_up[None], w_down[None]
    return w_gate, w_up, w_down


def _moe_experts_call(xs, group_sizes, w_gate, w_up, w_down, layer=0):
    """Raw pallas_call of the grouped SwiGLU: ``xs [M, D]`` (M a multiple
    of 128) against the stacks ``[L, E, D, F]``, ``[L, E, D, F]``, ``[L, E,
    F, D]``; returns ``[M, D]`` float32, rows of no group zero where a visit
    touched their tile and undefined elsewhere.  One block of the experts'
    width a visit where an expert fits VMEM, else ``F / f`` of them
    (``_moe_width_block``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    w_gate, w_up, w_down = _expert_stacks(w_gate, w_up, w_down)
    M, D = xs.shape
    E, F = w_gate.shape[1], w_gate.shape[3]
    tm = _MOE_ROWS
    f = _moe_width_block(D, F, w_gate.dtype.itemsize, tm)
    if f is None:
        raise ValueError(f"no block of experts of {D} x {F} fits "
                         f"{_MOE_BUDGET} bytes of VMEM")
    g, tile, starts, ends, n = _expert_visits(group_sizes, M, tm)
    scalars = (_layer_operand(layer), g, tile, starts, ends, n)
    visits = M // tm + E - 1
    out_shape = jax.ShapeDtypeStruct((M, D), jnp.float32)
    if f == F:
        def rows_map(v, layer, g, tile, *rest):
            del layer, g, rest
            return (tile[v], 0)

        def expert_map(v, layer, g, *rest):
            del rest
            return (layer[0], g[v], 0, 0)

        rows = pl.BlockSpec((tm, D), rows_map)
        call = _pallas_call(
            functools.partial(_moe_experts_kernel, tm=tm),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars),
                grid=(visits,),
                in_specs=[rows, pl.BlockSpec((1, 1, D, F), expert_map),
                          pl.BlockSpec((1, 1, D, F), expert_map),
                          pl.BlockSpec((1, 1, F, D), expert_map)],
                out_specs=rows),
            out_shape=out_shape,
            # a tile's output is built up over consecutive visits: in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_MOE_VMEM),
        )
        return call(*scalars, xs, w_gate, w_up, w_down)

    last = F // f - 1

    def rows_map(v, j, layer, g, tile, *rest):
        del j, layer, g, rest
        return (tile[v], 0)

    def block_of(v, j, n):
        # a visit past the live ones holds the last live turn's blocks
        return jnp.where(v < n[0], j, last)

    def in_map(v, j, layer, g, tile, starts, ends, n):
        del tile, starts, ends
        return (layer[0], g[v], 0, block_of(v, j, n))

    def down_map(v, j, layer, g, tile, starts, ends, n):
        del tile, starts, ends
        return (layer[0], g[v], block_of(v, j, n), 0)

    rows = pl.BlockSpec((tm, D), rows_map)
    call = _pallas_call(
        functools.partial(_moe_experts_kernel_blocked, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(visits, F // f),
            in_specs=[rows, pl.BlockSpec((1, 1, D, f), in_map),
                      pl.BlockSpec((1, 1, D, f), in_map),
                      pl.BlockSpec((1, 1, f, D), down_map)],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((tm, D), jnp.float32)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_MOE_VMEM),
    )
    return call(*scalars, xs, w_gate, w_up, w_down)


def _moe_experts_jnp(xs, group_sizes, w_gate, w_up, w_down, layer=0):
    """Reference and off-TPU body: three ``lax.ragged_dot`` over the same
    sorted rows, the activation between them; rows of no group give zero."""
    w_gate, w_up, w_down = (
        w[layer] if isinstance(layer, int)
        else lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
        for w in _expert_stacks(w_gate, w_up, w_down))
    sizes = group_sizes.astype(jnp.int32)
    a = lax.ragged_dot(xs, w_gate, sizes,
                       preferred_element_type=jnp.float32)
    b = lax.ragged_dot(xs, w_up, sizes, preferred_element_type=jnp.float32)
    h = (a * jax.nn.sigmoid(a) * b).astype(xs.dtype)
    return lax.ragged_dot(h, w_down, sizes,
                          preferred_element_type=jnp.float32)


def moe_experts_available(x_shape, w_shape, dtype=None):
    """True when the grouped-SwiGLU kernel can serve (rows are padded to
    whole tiles for it): lane-aligned widths, a block of the experts' width
    that fits VMEM (``_moe_width_block``), a TPU backend or interpret
    mode."""
    if x_shape[1] % _LANES or w_shape[-1] % _LANES:
        return False
    itemsize = 2 if dtype is None else jnp.dtype(dtype).itemsize
    if _moe_width_block(x_shape[1], w_shape[-1], itemsize) is None:
        return False
    return _kernels_enabled("grouped_experts")


def grouped_experts(xs, group_sizes, w_gate, w_up, w_down, *, layer=0):
    """The routed experts' SwiGLU over rows sorted by expert.

    xs           [M, D] the (token, expert) pairs' inputs, sorted by
                 expert; rows past ``sum(group_sizes)`` belong to none
    group_sizes  [E] int32, rows of each expert held here, in order
    w_gate/w_up  [L, E, D, F], w_down [L, E, F, D]: the stacks of every
                 layer, passed whole (or one layer's ``[E, ..]``)
    layer        which layer of the stacks (an int or a traced scalar)

    Returns ``[M, D]`` float32: ``W_d[e] (silu(W_g[e] x) * W_u[e] x)`` for a
    row of expert e; rows of no group are UNDEFINED (the kernel never
    visits a tile that holds none), so the caller masks them.  No row is
    dropped and no expert has a capacity."""
    if not moe_experts_available(xs.shape, w_gate.shape, xs.dtype):
        return _moe_experts_jnp(xs, group_sizes, w_gate, w_up, w_down, layer)
    M = xs.shape[0]
    xs = jnp.pad(xs, ((0, -M % _MOE_ROWS), (0, 0)))      # whole tiles of rows
    return _moe_experts_call(xs, group_sizes, w_gate, w_up, w_down,
                             layer)[:M]


# ---------------------------------------------------------------------------
# int8 weight-path matmul (quantized serving)
# ---------------------------------------------------------------------------
#
# y = dequant(quant(x) @ w_q): weights arrive pre-quantized (symmetric
# per-output-channel absmax int8 — inference/convert.py's rule), the
# kernel quantizes activations per row on the fly, runs the
# int8 x int8 -> int32 MXU dot, and dequantizes in the epilogue
# (acc * x_scale * w_scale -> out dtype).  K rides whole in the x/w
# blocks, so the per-row absmax — and therefore the whole computation —
# is independent of the (bm, bn) tiling; the jnp oracle below is the
# CPU fallback AND the parity reference.

_INT8_EPS = 1e-8  # activation absmax floor: all-zero rows quantize to 0


def quantize_int8(w):
    """Symmetric per-output-channel absmax int8 quantization of a
    matmul weight [..., K, N] (contraction axis second-to-last):
    returns (q int8 same shape, scale f32 [..., 1, N]).  All-zero and
    non-finite channels get a benign 1/127 scale (q == 0, dequant == 0)
    instead of a denormal that underflows when the scale is stored in a
    16-bit dtype — the ``_absmax_scale`` dead-channel guard, jnp
    edition."""
    w = jnp.asarray(w)
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2,
                   keepdims=True)
    amax = jnp.where(jnp.isfinite(amax) & (amax > 0.0), amax, 1.0)
    scale = (amax / 127.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _int8_matmul_jnp(x, w_q, w_scale):
    """Reference/fallback: bit-identical math to the kernel (dynamic
    per-row activation quant, exact int32 accumulation, f32 dequant
    epilogue).  x is 2D [M, K] here; ``int8_matmul`` handles leading
    dims."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    xs = jnp.maximum(amax, _INT8_EPS) * (1.0 / 127.0)
    xq = jnp.clip(jnp.round(xf / xs), -127, 127).astype(jnp.int8)
    acc = lax.dot_general(xq, w_q, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * xs
            * w_scale.astype(jnp.float32)).astype(x.dtype)


def _int8_matmul_kernel(x_ref, wq_ref, ws_ref, o_ref):
    """Grid point (i, j): x rows [i*bm, +bm) against weight columns
    [j*bn, +bn); K uncut, so the row absmax is exact per grid point."""
    x = x_ref[...].astype(jnp.float32)               # [bm, K]
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    xs = jnp.maximum(amax, _INT8_EPS) * (1.0 / 127.0)
    xq = jnp.clip(jnp.round(x / xs), -127, 127).astype(jnp.int8)
    acc = lax.dot(xq, wq_ref[...],                   # int8 x int8 MXU
                  preferred_element_type=jnp.int32)
    o_ref[...] = (acc.astype(jnp.float32) * xs
                  * ws_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def int8_matmul_block_specs(M, K, N, bm, bn):
    """(block, array) shape pairs for the int8 matmul — the single
    source of truth shared by the call site, the candidate generator,
    and the Level-3 verifier."""
    return {"in": [((bm, K), (M, K)),        # x (activations)
                   ((K, bn), (K, N)),        # w_q (int8 weights)
                   ((1, bn), (1, N))],       # w_scale (per-channel f32)
            "out": [((bm, bn), (M, N))]}


def _int8_matmul_call(x, w_q, w_scale, *, bm, bn):
    """Raw pallas_call for the int8 weight-matmul kernel."""
    from jax.experimental import pallas as pl
    M, K = x.shape
    N = w_q.shape[1]
    specs = int8_matmul_block_specs(M, K, N, bm, bn)

    def x_map(i, j):
        del j
        return (i, 0)

    def w_map(i, j):
        del i
        return (0, j)

    def o_map(i, j):
        return (i, j)

    return _pallas_call(
        _int8_matmul_kernel,
        grid=(M // bm, N // bn),
        in_specs=[pl.BlockSpec(specs["in"][0][0], x_map),
                  pl.BlockSpec(specs["in"][1][0], w_map),
                  pl.BlockSpec(specs["in"][2][0], w_map)],
        out_specs=pl.BlockSpec(specs["out"][0][0], o_map),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=_compiler_params("parallel", "parallel"),
    )(x, w_q, w_scale)


def _int8_keys(M, K, N, dtype=None):
    """Lookup-key chain for the tuned (bm, bn): context-qualified
    first, shape-only fallback."""
    from paddle_tpu.ops import autotune
    keys = []
    if dtype is not None:
        keys.append(["blocks", int(M), int(K), int(N)]
                    + autotune.context_key(str(jnp.dtype(dtype))))
    keys.append(["blocks", int(M), int(K), int(N)])
    return keys


def _int8_blocks_legal(bm, bn, M, K, N):
    if M % bm or N % bn:
        return False
    specs = int8_matmul_block_specs(M, K, N, bm, bn)
    return all(mosaic_block_legal(blk, arr, dtype_bits=8)
               for blk, arr in specs["in"] + specs["out"])


def _int8_matmul_config(M, K, N, dtype=None):
    """Resolve (bm, bn): tuned value if cached and still legal for this
    shape, else the largest power-of-two divisors (whole axis when none
    divides)."""
    from paddle_tpu.ops import autotune
    cfg = autotune.lookup_chain("int8_matmul", _int8_keys(M, K, N, dtype))
    if cfg is not None:
        bm, bn = int(cfg[0]), int(cfg[1])
        if _int8_blocks_legal(bm, bn, M, K, N):
            return bm, bn
    bm = next((b for b in (256, 128) if M % b == 0), M)
    bn = next((b for b in (256, 128) if N % b == 0), N)
    return bm, bn


def int8_matmul_available(x_shape, wq_shape, dtype=None):
    """True when the Pallas int8 path can serve this problem: the MXU
    dot wants a lane-aligned contraction (K % 128 == 0) and output
    width (N % 128 == 0) plus at least one sublane tile of rows;
    everything else — notably the debug presets' tiny hidden sizes —
    is served by the jnp oracle."""
    del dtype
    M, K = x_shape
    N = wq_shape[1]
    if K % _LANES != 0 or N % _LANES != 0 or M < 8:
        return False
    return _kernels_enabled("int8_matmul")


def int8_matmul(x, w_q, w_scale, *, bm=None, bn=None):
    """Activation-dynamic int8 matmul: y = dequant(quant_row(x) @ w_q).

    x        [..., K] activations, any float dtype
    w_q      [K, N] int8 weights (``quantize_int8`` layout)
    w_scale  [1, N] (or [N]) f32 per-output-channel scales

    Returns [..., N] in x.dtype.  The jnp oracle (the same math) serves
    off-TPU and lane-unaligned shapes; a kernel that fails to lower or
    compile raises."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w_q.shape[1]
    x2 = x.reshape(-1, K)
    ws = jnp.asarray(w_scale).reshape(1, N)

    def ref():
        return _int8_matmul_jnp(x2, w_q, ws).reshape(*lead, N)

    if not int8_matmul_available(x2.shape, w_q.shape, x.dtype):
        return ref()
    M = x2.shape[0]
    if bm is None or bn is None:
        cm, cn = _int8_matmul_config(M, K, N, x.dtype)
        bm = bm or cm
        bn = bn or cn
    if not _int8_blocks_legal(bm, bn, M, K, N):
        return ref()
    return _int8_matmul_call(x2, w_q, ws, bm=bm, bn=bn).reshape(*lead, N)


def int8_matmul_candidates(M, K, N, dtype=jnp.bfloat16):
    """Legal (bm, bn) candidates via ``autotune.legal_candidates`` over
    the real block specs — Mosaic-illegal or VMEM-busting shapes are
    unrepresentable rather than filtered late."""
    from paddle_tpu.ops import autotune
    pool = sorted({(bm, bn)
                   for bm in set(_POW2_BLOCKS) | {M}
                   for bn in set(_POW2_BLOCKS) | {N}
                   if M % bm == 0 and N % bn == 0})

    def spec_fn(cand):
        bm, bn = cand
        specs = int8_matmul_block_specs(M, K, N, bm, bn)
        # resident VMEM: x f32 + xq int8 + w_q int8 + scale + out f32
        resident = bm * K * 5 + K * bn + bn * 4 + bm * bn * 4
        if resident > _VMEM_BUDGET:
            return None
        return specs["in"] + specs["out"]

    return autotune.legal_candidates(pool, spec_fn, dtype_bits=8)


def _verify_int8_candidate(M, K, N, dtype):
    """autotune verify hook: refute a (bm, bn) candidate with the
    Level-3 verifier before any compile."""
    def verify(cand):
        from paddle_tpu.analysis import kernel_checks as _kc
        bm, bn = cand
        avals = (jax.ShapeDtypeStruct((M, K), dtype),
                 jax.ShapeDtypeStruct((K, N), jnp.int8),
                 jax.ShapeDtypeStruct((1, N), jnp.float32))

        def fwd(x, wq, ws):
            return _int8_matmul_call(x, wq, ws, bm=bm, bn=bn)

        found = _kc.verify_kernel(fwd, *avals,
                                  name=f"int8_matmul[{bm}x{bn}]")
        return [f"{f.rule}: {f.message}" for f in found
                if f.severity == "error"]
    return verify


def tune_int8_matmul(M=256, K=512, N=512, dtype=jnp.bfloat16,
                     budget_s=None, verbose=False):
    """Autotune (bm, bn) for one int8 weight-matmul shape (requires
    N >= K for the timing chain's feedback slice).  Cached result
    short-circuits; off-TPU (and not interpret) returns None without
    touching the tuner."""
    import time

    import numpy as np

    from paddle_tpu.ops import autotune
    cached = autotune.lookup_chain("int8_matmul",
                                   _int8_keys(M, K, N, dtype))
    if cached is not None:
        return tuple(int(c) for c in cached)
    if not (_on_tpu() or _INTERPRET):
        return None
    if N < K:
        raise ValueError(f"tune_int8_matmul needs N >= K, got K={K} N={N}")

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.standard_normal((M, K)), dtype)
    wq, ws = quantize_int8(
        jnp.asarray(rng.standard_normal((K, N)) * 0.05, jnp.float32))
    n_chain = 8

    def time_candidate(cand):
        bm, bn = cand

        @jax.jit
        def chained(xc):
            def body(xx, _):
                o = _int8_matmul_call(xx, wq, ws, bm=bm, bn=bn)
                return xx + o[:, :K] * jnp.asarray(1e-6, xx.dtype), None
            xf, _ = lax.scan(body, xc, None, length=n_chain)
            return jnp.sum(xf[0])

        chained(x).block_until_ready()       # compile
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            chained(x).block_until_ready()
            best = min(best, (time.perf_counter() - t0) / n_chain)
        return best

    key = _int8_keys(M, K, N, dtype)[0]
    return autotune.tune(
        "int8_matmul", key,
        int8_matmul_candidates(M, K, N, dtype),
        time_candidate, budget_s=budget_s, verbose=verbose,
        verify_candidate=_verify_int8_candidate(M, K, N, dtype))


# ---------------------------------------------------------------------------
# Level-3 kernel-verification registry
# ---------------------------------------------------------------------------

def kernel_verify_cases():
    """(name, traceable fn, example avals) for every shipped Pallas
    kernel — the registry the Level-3 verifier
    (``analysis/kernel_checks.verify_registered``) and the CLI
    ``tools/tpu_lint.py --kernels`` sweep.  Everything here runs under
    ``jax.eval_shape`` only: no TPU, no execution, a few ms per case.

    Shapes are representative, not exhaustive: one streamed flash shape
    (S past the resident cutoff), one resident shape (the parity-case
    S=256), f32 and bf16 for the streamed forward (the bf16 case proves
    the dtype-aware Mosaic check against the f32 scratch accumulators),
    and the fused decoder-block kernels driven fwd+bwd through their
    custom_vjp so the backward kernels are captured too."""
    SDS = jax.ShapeDtypeStruct
    f32 = jnp.float32
    D, bq, bk = 128, _BQ, _BK
    S_str, S_res = 512, 256

    def qkv_avals(S, BH=2, dtype=f32):
        return tuple(SDS((BH, S, D), dtype) for _ in range(3))

    def bwd_avals(S, BH=2, dtype=f32):
        return qkv_avals(S, BH, dtype) + (
            SDS((BH, S, D), dtype),              # g
            SDS((BH, S, D), dtype),              # o
            SDS((BH, S, _LANES), jnp.float32))   # lse

    def fwd_streamed(q, k, v):
        return _flash_fwd_streamed(q, k, v, bq, bk)

    def bwd_streamed(q, k, v, g, o, lse):
        return _flash_bwd_streamed(q, k, v, g, o, lse, bq, bk)

    def fwd_resident(q, k, v):
        return _flash_fwd_resident(q, k, v, bq, bk)

    def bwd_resident(q, k, v, g, o, lse):
        return _flash_bwd_resident(q, k, v, g, o, lse, bq, bk)

    cases = [
        ("flash_fwd_streamed", fwd_streamed, qkv_avals(S_str)),
        ("flash_fwd_streamed_bf16", fwd_streamed,
         qkv_avals(S_str, dtype=jnp.bfloat16)),
        ("flash_bwd_streamed", bwd_streamed, bwd_avals(S_str)),
        ("flash_fwd_resident", fwd_resident, qkv_avals(S_res)),
        ("flash_bwd_resident", bwd_resident, bwd_avals(S_res)),
    ]

    # fused decoder-block kernels at the parity-case shapes, fwd+bwd
    # through the custom_vjp (captures the qkv/epilogue/mlp kernels AND
    # the fused flash backward re-indexed over the flattened layout)
    B, S, H, I = 1, 256, 256, 512
    eps = 1e-6
    attn_cfg = _fused_attn_config(S, H, D, f32)
    mlp_cfg = _fused_mlp_config(S, H, I, f32)
    x = SDS((B, S, H), f32)
    ln = SDS((H,), f32)
    w = SDS((H, H), f32)
    rope = SDS((S, D), f32)
    dy = SDS((B, S, H), f32)

    if attn_cfg is not None:
        abq, abk = attn_cfg

        def attn_fwd_bwd(x, ln, wq, wk, wv, wo, sin, cos, dy):
            f = lambda t: _fused_attention_call(  # noqa: E731
                (D, eps, abq, abk), t, ln, wq, wk, wv, wo, sin, cos)
            y, pull = jax.vjp(f, x)
            return y, pull(dy)

        cases.append(("fused_attention_block", attn_fwd_bwd,
                      (x, ln, w, w, w, w, rope, rope, dy)))

    if mlp_cfg is not None:
        bs, bi = mlp_cfg
        wg = SDS((H, I), f32)
        wd = SDS((I, H), f32)

        def mlp_fwd_bwd(x, ln, wg_, wu_, wd_, dy):
            f = lambda t: _fused_mlp_call(  # noqa: E731
                (eps, bs, bi), t, ln, wg_, wu_, wd_)
            y, pull = jax.vjp(f, x)
            return y, pull(dy)

        cases.append(("fused_mlp_block", mlp_fwd_bwd,
                      (x, ln, wg, wg, wd, dy)))

    # ragged paged attention: mixed prefill+decode and the decode-only
    # (Tc == 1) specialization.  The cases close over CONCRETE numpy
    # block tables / lengths, which is what lets the verifier bound the
    # page ids the kernel's own copies read (the call declares them,
    # ``dma_indexes``) instead of skipping them — an out-of-range table
    # entry here would fire index-oob.
    import numpy as np
    Rr, nkv, rep, page = 4, 2, 2, _LANES
    P, Bmax, Ls, layer = 16, 4, 3, 2
    # the engine's form: the stacked pools of Ls layers and a layer other
    # than 0, so the layer is bounded with the table
    kv_aval = SDS((Ls, nkv, P, page, D), f32)
    tbl = (1 + np.arange(Rr * Bmax, dtype=np.int32)
           % (P - 1)).reshape(Rr, Bmax)
    lens = np.full((Rr,), Bmax * page, dtype=np.int32)

    def rpa_case(Tc):
        Tr = Tc * rep
        qlens = np.full((Rr,), Tc, dtype=np.int32)

        def fwd(q, kp, vp):
            return _rpa_call(q, kp, vp, tbl, lens, qlens, rep=rep,
                             layer=layer)
        return fwd, (SDS((Rr, nkv, Tr, D), f32), kv_aval, kv_aval)

    mixed_fn, mixed_avals = rpa_case(8)
    decode_fn, decode_avals = rpa_case(1)
    cases.append(("ragged_paged_attention", mixed_fn, mixed_avals))
    cases.append(("ragged_paged_attention_decode", decode_fn,
                  decode_avals))
    # the speculative-decoding verify bucket: the target checks k draft
    # tokens in one step as a short ragged prefill (Tc = 1 + k; k = 3
    # matches SpecDecodeConfig's default).  Same kernel, distinct
    # compiled shape — registering it keeps the Level-3 sweep proving
    # the block table at the shape serving actually runs.
    spec_fn, spec_avals = rpa_case(4)
    cases.append(("ragged_paged_attention_spec_verify", spec_fn,
                  spec_avals))

    # quantized-KV ragged paged attention: int8 pools, with the
    # per-page scale pools riding as CONCRETE scalar-prefetch operands
    # — concrete so the verifier bounds table and layer at the extended
    # 6-scalar signature, and so the VMEM estimate's scalar-operand
    # accounting sees the real per-layer scale shapes ([nkv, P], sliced
    # out of the stack's [Ls, nkv, P]).
    ksc = np.ones((Ls, nkv, P), dtype=np.float32)
    vsc = np.ones((Ls, nkv, P), dtype=np.float32)
    Tc_q = 8
    qlens_q = np.full((Rr,), Tc_q, dtype=np.int32)
    kv_i8 = SDS((Ls, nkv, P, page, D), jnp.int8)

    def rpa_quant_fwd(q, kp, vp):
        return _rpa_call(q, kp, vp, tbl, lens, qlens_q, rep=rep,
                         k_scales=ksc, v_scales=vsc, layer=layer)

    cases.append(("ragged_paged_attention_quant_kv", rpa_quant_fwd,
                  (SDS((Rr, nkv, Tc_q * rep, D), f32), kv_i8, kv_i8)))

    # the paged KV write into the same stacked pools: a chunk that
    # starts mid-tile (two tiles a request) and the one-token decode
    # write.  Block legality and the VMEM estimate are checked; its
    # (layer, page, tile) index maps read tables that jnp derives from
    # the block table, so they stay traced and are not evaluated (nor is
    # output coverage, which an aliased in-place output does not owe)
    def kv_write_case(Tc):
        qlens = np.full((Rr,), Tc, dtype=np.int32)
        lens_w = np.full((Rr,), page + 5 + Tc, dtype=np.int32)

        def fwd(kn, vn, kp, vp):
            return _pools_write_call((kp, vp), (kn, vn), tbl, lens_w,
                                     qlens, layer)
        new = SDS((Rr, Tc, nkv, D), f32)
        return fwd, (new, new, kv_aval, kv_aval)

    for name, Tc in (("paged_kv_write", 8), ("paged_kv_write_decode", 1)):
        fn, avals = kv_write_case(Tc)
        cases.append((name, fn, avals))

    # the selective scan of a Mamba layer, both programs, on the compact
    # flat batch: a layer other than 0 of a stack of Ls, two groups of 8
    # rows of which one holds a whole chunk beside decode rows and an idle
    # row; concrete lengths, so the state's (layer, group, tile) index map
    # is evaluated
    Rs, Es, Ns = 16, 2 * _LANES, 16

    def scan_case(Tc):
        qlens = np.array([1] * 8 + [Tc, 1, 0, 1, 1, 1, 1, 1], np.int32)
        fresh = qlens > 1
        start = np.cumsum(qlens) - qlens
        Ts = -(-int(qlens.sum()) // 8) * 8       # the flat batch, compact

        def fwd(ssm, dt, x, bm, cm, a):
            return _ssm_scan_call(ssm, dt, x, bm, cm, a, qlens, start,
                                  fresh, layer, Tc)
        row = SDS((Ts, Es), f32)
        col = SDS((Ts, Ns), f32)
        return fwd, (SDS((Ls, Ns, Rs, Es), f32), row, row, col, col,
                     SDS((Ns, Es), f32))

    for name, Tc in (("selective_scan", 16), ("selective_scan_decode", 1)):
        fn, avals = scan_case(Tc)
        cases.append((name, fn, avals))

    # latent pages (multi-head latent attention, absorbed): the walk over
    # ONE pool whose first 128 lanes are the value, 4 query heads on the one
    # latent "head", both programs; and the latent's write, the write kernel
    # on one pool.  Concrete tables, as above
    lat_aval = SDS((Ls, 1, P, page, 2 * D), f32)

    def latent_case(Tc):
        qlens = np.full((Rr,), Tc, dtype=np.int32)

        def fwd(q, pg):
            return _rpa_latent_call(q, pg, tbl, lens, qlens, rep=4,
                                    v_lanes=D, scale=0.1, layer=layer)
        return fwd, (SDS((Rr, 1, Tc * 4, 2 * D), f32), lat_aval)

    for name, Tc in (("latent_paged_attention", 8),
                     ("latent_paged_attention_decode", 1)):
        fn, avals = latent_case(Tc)
        cases.append((name, fn, avals))

    def latent_write(new, pg):
        qlens = np.full((Rr,), 8, dtype=np.int32)
        lens_w = np.full((Rr,), page + 5 + 8, dtype=np.int32)
        return _pools_write_call((pg,), (new,), tbl, lens_w, qlens, layer)

    cases.append(("paged_latent_write", latent_write,
                  (SDS((Rr, 8, 1, 2 * D), f32), lat_aval)))

    # the routed experts' grouped SwiGLU: three tiles of rows over five
    # experts of a stack of Ls layers, one expert empty, one over two tiles;
    # concrete group sizes, so the (layer, expert) and tile maps are
    # evaluated
    Em, Dm, Fm = 5, 2 * _LANES, _LANES
    sizes = np.array([100, 0, 150, 3, 40], np.int32)

    def experts_case(xs, wg_, wu_, wd_):
        return _moe_experts_call(xs, sizes, wg_, wu_, wd_, layer)

    cases.append(("grouped_experts", experts_case,
                  (SDS((3 * _MOE_ROWS, Dm), f32), SDS((Ls, Em, Dm, Fm), f32),
                   SDS((Ls, Em, Dm, Fm), f32), SDS((Ls, Em, Fm, Dm), f32))))

    # int8 weight-path matmul at a representative lane-aligned shape
    Mq, Kq, Nq = 256, 256, 256

    def int8_case(x, wq, ws):
        return _int8_matmul_call(x, wq, ws, bm=128, bn=128)

    cases.append(("int8_matmul", int8_case,
                  (SDS((Mq, Kq), f32), SDS((Kq, Nq), jnp.int8),
                   SDS((1, Nq), f32))))
    return cases


def _verify_flash_candidate(BH, S, D, dtype):
    """autotune verify hook: refute a (bq, bk) flash candidate with the
    Level-3 verifier before any compile. Returns error messages."""
    def verify(cand):
        from paddle_tpu.analysis import kernel_checks as _kc
        bq, bk = cand
        avals = tuple(jax.ShapeDtypeStruct((BH, S, D), dtype)
                      for _ in range(3))

        def fwd(q, k, v):
            return _flash_fwd(q, k, v, bq, bk)

        found = _kc.verify_kernel(fwd, *avals,
                                  name=f"flash_fwd[{bq}x{bk}]")
        return [f"{f.rule}: {f.message}" for f in found
                if f.severity == "error"]
    return verify


# register with the Level-3 verifier at import time (lazy provider: the
# cases above are only built when a sweep actually runs)
try:
    from paddle_tpu.analysis import kernel_checks as _kernel_checks
except ImportError:  # pruned install without the analysis package
    _kernel_checks = None
if _kernel_checks is not None:
    _kernel_checks.register_kernel_provider("ops.pallas_ops",
                                            kernel_verify_cases)
