"""Level-3 lint, part (a): the Pallas kernel verifier.

Levels 1–2 look at jaxprs and Python source; neither sees *inside* a
``pl.pallas_call``.  This module does, without a TPU and without running
anything: a tracing shim temporarily replaces
``jax.experimental.pallas.pallas_call`` and the target function is
abstractly evaluated with ``jax.eval_shape``.  Every pallas_call site
executed during the trace is captured — kernel function, grid,
BlockSpecs, out_shape, scratch shapes, operand avals, and the exact
call-site file:line — and then checked against the rules below.
BENCH_r02 lost a bench round to an illegal block spec that Mosaic only
rejected at compile time on-device; every rule here fires on CPU at
trace time instead.

============================  =========  ====================================
rule                          severity   hazard
============================  =========  ====================================
kernel-grid-divisibility      error      grid x block_shape does not tile an
                                         operand evenly — the edge block is
                                         padded (read) / partially written
kernel-index-oob              error      an index_map emits a block index
                                         outside the operand (the classic
                                         off-by-one ``i + 1``) — Mosaic
                                         reads/writes out of bounds; or a
                                         scalar-prefetch table the kernel
                                         indexes an HBM operand with by its
                                         own DMA (declared in the call's
                                         ``metadata["dma_indexes"]``) holds
                                         an entry outside that axis
kernel-output-coverage        error      some output block is never emitted
                                         by any grid point — silent garbage
                                         in the uncovered region (not for an
                                         output aliased to an input: there
                                         the input's values stay)
kernel-mosaic-block           error      a derived block violates Mosaic
                                         tiling for the *actual* dtype
                                         (``autotune.mosaic_block_legal``)
kernel-vmem-budget            warning    estimated VMEM footprint (resident
                                         blocks + scratch) exceeds the
                                         per-generation budget
kernel-unused-ref             warning    an output or scratch ref the kernel
                                         body never touches — dead VMEM
kernel-narrow-accumulator     warning    a bf16/f16 scratch accumulator over
                                         bf16/f16 inputs — accumulate in f32
kernel-verifier-error         warning    a registered kernel case failed to
                                         trace at all (itself a red flag)
============================  =========  ====================================

Proven vs. heuristic: when ``prod(grid)`` is at or under
``index_eval_points`` the index maps are evaluated over the *entire*
grid, so in-bounds access and output coverage are proved, not sampled.
Above the cap only the grid corners are evaluated (bounds stay sound for
monotone affine maps — everything shipped here — but coverage is
skipped) and the finding notes the downgrade.

Like the rest of the package this module imports without jax; jax is
only touched inside :func:`verify_kernel` / :func:`capture_sites`.
"""
from __future__ import annotations

import ast
import contextlib
import functools
import inspect
import itertools
import json
import math
import sys
import textwrap
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from . import core as _core
from .core import ERROR, WARNING, Finding

__all__ = ["KERNEL_RULES", "DEFAULT_KERNEL_CONFIG", "KernelSite",
           "capture_sites", "check_sites", "verify_kernel",
           "verify_registered", "verify_module", "register_kernel_case",
           "register_kernel_provider", "registered_cases"]

# rule id -> (severity, one-line doc).  Checks are methods of the site
# checker below rather than free functions: they share one normalized
# view of the call.
KERNEL_RULES: Dict[str, tuple] = {
    "kernel-grid-divisibility": (
        ERROR, "grid x block_shape does not tile an operand evenly"),
    "kernel-index-oob": (
        ERROR, "index_map emits a block index outside the operand"),
    "kernel-output-coverage": (
        ERROR, "some output block is never written by any grid point"),
    "kernel-mosaic-block": (
        ERROR, "block shape violates Mosaic tiling for the actual dtype"),
    "kernel-vmem-budget": (
        WARNING, "estimated VMEM footprint exceeds the generation budget"),
    "kernel-unused-ref": (
        WARNING, "output/scratch ref the kernel body never references"),
    "kernel-narrow-accumulator": (
        WARNING, "bf16/f16 scratch accumulator over bf16/f16 inputs"),
    "kernel-verifier-error": (
        WARNING, "registered kernel case failed to trace"),
}

DEFAULT_KERNEL_CONFIG: Dict[str, Any] = {
    # explicit budget override (bytes).  None -> pick by device generation.
    "vmem_budget_bytes": None,
    # per-generation VMEM budgets: ~16 MiB/core on v4/v5, double on v6e,
    # minus headroom for Mosaic's own double-buffering and spills (the
    # same margin ops/pallas_ops uses to prefilter autotune candidates).
    "vmem_budgets": {"v4": 12 << 20, "v5e": 12 << 20, "v5p": 12 << 20,
                     "v6e": 24 << 20, "default": 12 << 20},
    # full index-map enumeration cap: grids up to this many points are
    # proved exhaustively; larger grids fall back to corner sampling.
    "index_eval_points": 1 << 16,
}

_NARROW_FLOATS = ("bfloat16", "float16")


# ---------------------------------------------------------------------------
# capture: a context manager that swaps jax.experimental.pallas.pallas_call
# for a recording shim.  ops/pallas_ops.py resolves ``pl.pallas_call`` at
# call time, so the swap intercepts every site traced inside the block.
# ---------------------------------------------------------------------------

class KernelSite:
    """One captured ``pl.pallas_call`` invocation (normalized)."""

    def __init__(self, kernel, grid, in_specs, out_specs, out_shapes,
                 scratch_shapes, file, line, num_scalar_prefetch=0):
        self.kernel = kernel
        self.grid: Tuple[int, ...] = grid
        self.in_specs = in_specs          # list[BlockSpec | None]
        self.out_specs = out_specs        # list[BlockSpec | None]
        self.out_shapes = out_shapes      # list[ShapeDtypeStruct-like]
        self.scratch_shapes = scratch_shapes
        self.file = file
        self.line = line
        # PrefetchScalarGridSpec: the first N operands are SMEM scalar
        # refs handed to every index_map after the grid indices
        self.num_scalar_prefetch = int(num_scalar_prefetch)
        self.operands: list = []          # avals, filled at the inner call
        self.scalar_operands: list = []   # leading scalar-prefetch args
        # (scalar operand, input operand, axis): the kernel reads that
        # input in HBM by its own DMA at indexes it takes from that scalar
        # operand — no index map carries them
        self.dma_indexes: List[Tuple[int, int, int]] = []
        # outputs that are an input's buffer (``input_output_aliases``): a
        # block no grid point writes holds what the input held
        self.aliased_outputs: frozenset = frozenset()

    @property
    def kernel_name(self) -> str:
        fn = self.kernel
        while isinstance(fn, functools.partial):
            fn = fn.func
        return getattr(fn, "__name__", repr(fn))


def _as_tuple(x) -> tuple:
    if x is None:
        return ()
    if isinstance(x, (tuple, list)):
        return tuple(x)
    return (x,)


def _tree_leaves(x, is_leaf):
    """Tiny pytree flattener (dict/list/tuple) — avoids importing jax
    tree utils for what is always a shallow structure here."""
    if x is None:
        return []
    if is_leaf(x):
        return [x]
    if isinstance(x, dict):
        out = []
        for k in sorted(x):
            out.extend(_tree_leaves(x[k], is_leaf))
        return out
    if isinstance(x, (tuple, list)):
        out = []
        for item in x:
            out.extend(_tree_leaves(item, is_leaf))
        return out
    return [x]


def _normalize_call(kernel, args, kwargs, blockspec_cls, file, line
                    ) -> Optional[KernelSite]:
    """Build a KernelSite from raw pallas_call arguments; None if the
    call uses a shape this verifier does not model (grid_spec objects
    with no recoverable grid, etc.)."""
    out_shape = kwargs.get("out_shape")
    if out_shape is None and len(args) > 0:
        out_shape = args[0]
    grid = kwargs.get("grid", ())
    in_specs = kwargs.get("in_specs")
    out_specs = kwargs.get("out_specs")
    scratch = kwargs.get("scratch_shapes", ())
    grid_spec = kwargs.get("grid_spec")
    nsp = 0
    if grid_spec is not None:  # pl.GridSpec / PrefetchScalarGridSpec
        grid = getattr(grid_spec, "grid", grid)
        in_specs = getattr(grid_spec, "in_specs", in_specs)
        out_specs = getattr(grid_spec, "out_specs", out_specs)
        scratch = getattr(grid_spec, "scratch_shapes", scratch)
        try:
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
        except (TypeError, ValueError):
            nsp = 0
    if isinstance(grid, int):
        grid = (grid,)
    try:
        grid = tuple(int(g) for g in _as_tuple(grid))
    except (TypeError, ValueError):
        return None  # dynamic grid — out of scope
    is_spec = lambda s: isinstance(s, blockspec_cls)
    is_shape = lambda s: hasattr(s, "shape") and hasattr(s, "dtype")
    site = KernelSite(
        kernel=kernel,
        grid=grid,
        in_specs=[s if is_spec(s) else None
                  for s in _tree_leaves(in_specs, is_spec)],
        out_specs=[s if is_spec(s) else None
                   for s in _tree_leaves(out_specs, is_spec)],
        out_shapes=_tree_leaves(out_shape, is_leaf=is_shape),
        scratch_shapes=_tree_leaves(_as_tuple(scratch), is_leaf=is_shape),
        file=file, line=line, num_scalar_prefetch=nsp)
    site.aliased_outputs = frozenset(
        int(o) for o in (kwargs.get("input_output_aliases") or {}).values())
    declared = (kwargs.get("metadata") or {}).get("dma_indexes")
    if declared:
        site.dma_indexes = [tuple(int(i) for i in triple)
                            for triple in json.loads(declared)]
    return site


@contextlib.contextmanager
def capture_sites(sites: List[KernelSite]):
    """Swap ``pl.pallas_call`` for a shim that records every call site
    (and its operand avals) into ``sites`` while delegating to the real
    pallas_call, so tracing behaves identically. A no-op (still a valid
    context) when jax/pallas is unavailable."""
    try:
        import jax  # noqa: F401  (ensures jax present before patching)
        from jax.experimental import pallas as pl
    except ImportError:
        yield sites
        return

    real = pl.pallas_call
    blockspec_cls = pl.BlockSpec

    def shim(kernel, *args, **kwargs):
        fr = sys._getframe(1)
        if fr.f_code.co_name == "_pallas_call":
            # ops/pallas_ops.py makes its calls through one helper; the
            # site is the helper's caller
            fr = fr.f_back
        site = _normalize_call(kernel, args, kwargs, blockspec_cls,
                               fr.f_code.co_filename, fr.f_lineno)
        wrapped = real(kernel, *args, **kwargs)
        if site is None:
            return wrapped

        @functools.wraps(wrapped)
        def with_operands(*operands, **okw):
            ops = [o for o in operands
                   if hasattr(o, "shape") and hasattr(o, "dtype")]
            # scalar-prefetch operands lead; they live in SMEM and pair
            # with no BlockSpec, so keep them out of the grid operands
            site.scalar_operands = ops[:site.num_scalar_prefetch]
            site.operands = ops[site.num_scalar_prefetch:]
            sites.append(site)
            return wrapped(*operands, **okw)
        return with_operands

    pl.pallas_call = shim
    try:
        yield sites
    finally:
        pl.pallas_call = real


# ---------------------------------------------------------------------------
# the per-site checker
# ---------------------------------------------------------------------------

def _mosaic_legal() -> Callable:
    """The shared Mosaic tiling predicate.  Prefer the autotune export
    (one source of truth with candidate filtering); fall back to a local
    copy when analysis is loaded standalone without the package."""
    try:
        from paddle_tpu.ops.autotune import mosaic_block_legal
        return mosaic_block_legal
    except ImportError:
        return _mosaic_block_legal_fallback


def _mosaic_block_legal_fallback(block_shape, array_shape,
                                 dtype_bits: int = 32) -> bool:
    # mirror of ops/pallas_ops.mosaic_block_legal — keep in sync.
    if len(block_shape) != len(array_shape):
        return False
    if len(block_shape) >= 2:
        *_, sub, lane = block_shape
        *_, asub, alane = array_shape
        if lane % 128 != 0 and lane != alane:
            return False
        if sub % 8 != 0 and sub != asub:
            return False
        return True
    if len(block_shape) == 1:
        packing = max(1, 32 // max(1, dtype_bits))
        return (block_shape[0] % (128 * packing) == 0
                or block_shape[0] == array_shape[0])
    return True


def _dtype_name(dtype) -> str:
    """Canonical dtype name: accepts numpy dtypes, jax scalar classes
    (``jnp.bfloat16`` — what pltpu.VMEM stores), and strings."""
    try:
        import numpy as np
        return str(np.dtype(dtype))
    except (ImportError, TypeError):
        return str(dtype)


def _dtype_itemsize(dtype) -> int:
    size = getattr(dtype, "itemsize", None)
    if size:
        return int(size)
    name = _dtype_name(dtype)
    if name in _NARROW_FLOATS or name in ("int16", "uint16"):
        return 2
    if name in ("int8", "uint8", "bool",
                "float8_e4m3fn", "float8_e5m2"):
        return 1
    if name in ("float64", "int64", "uint64", "complex64"):
        return 8
    return 4


def _block_dims(spec, array_shape) -> Optional[Tuple[int, ...]]:
    """Concrete per-dim block sizes for a spec over an array, or None
    when the spec covers the whole array (no blocking)."""
    bshape = getattr(spec, "block_shape", None) if spec is not None else None
    if bshape is None:
        return None
    dims = []
    for d, b in enumerate(bshape):
        if b is None:  # squeezed dim: block extent 1
            dims.append(1)
        else:
            try:
                dims.append(int(b))
            except (TypeError, ValueError):
                return None
    if len(dims) != len(array_shape):
        return None  # rank mismatch — pallas itself rejects this later
    return tuple(dims)


def _is_blocked(spec) -> bool:
    mode = getattr(spec, "indexing_mode", None)
    if mode is None:
        return True
    return type(mode).__name__ in ("Blocked", "blocked")


def _in_hbm(spec) -> bool:
    """The operand stays where it is (``memory_space=pl.ANY`` / HBM): no
    block of it is brought to VMEM, the kernel reads it by DMA."""
    space = getattr(spec, "memory_space", None)
    return getattr(space, "name", str(space)).upper() in ("ANY", "HBM")


class _Operand:
    """One (array, spec) pair the grid iterates over."""

    def __init__(self, role, index, shape, dtype, spec):
        self.role = role        # "in" | "out"
        self.index = index
        self.shape = tuple(int(d) for d in shape)
        self.dtype = dtype
        self.spec = spec
        self.blocks = _block_dims(spec, self.shape)

    @property
    def label(self) -> str:
        return f"{self.role}[{self.index}]"

    def grid_blocks(self) -> Tuple[int, ...]:
        """Blocks needed per dim to cover the array (ceil division)."""
        return tuple(-(-a // b) for a, b in zip(self.shape, self.blocks))


def _grid_points(grid: Tuple[int, ...], cap: int):
    """(points, exhaustive): the full grid when small enough to prove
    properties, otherwise the corner set (bounds-only heuristic)."""
    total = math.prod(grid) if grid else 0
    if total == 0:
        return [], True
    if total <= cap:
        return list(itertools.product(*(range(g) for g in grid))), True
    corners = itertools.product(*({0, g - 1} for g in grid))
    return list(corners), False


class _SiteChecker:
    def __init__(self, site: KernelSite, cfg: dict,
                 name: Optional[str], rules):
        self.site = site
        self.cfg = cfg
        self.name = name
        self.rules = rules
        self.findings: List[Finding] = []

    def _want(self, rule: str) -> bool:
        return self.rules is None or rule in self.rules

    def _emit(self, rule: str, msg: str, file=None, line=None, **extra):
        severity, _ = KERNEL_RULES[rule]
        extra.setdefault("kernel", self.site.kernel_name)
        self.findings.append(Finding(
            rule=rule, severity=severity, message=msg,
            file=file or self.site.file, line=line or self.site.line,
            function=self.name, source="kernel", extra=extra))

    def _operands(self) -> List[_Operand]:
        s = self.site
        ops = []
        for i, o in enumerate(s.operands):
            spec = s.in_specs[i] if i < len(s.in_specs) else None
            ops.append(_Operand("in", i, o.shape, o.dtype, spec))
        for i, o in enumerate(s.out_shapes):
            spec = s.out_specs[i] if i < len(s.out_specs) else None
            ops.append(_Operand("out", i, o.shape, o.dtype, spec))
        return ops

    def run(self) -> List[Finding]:
        ops = self._operands()
        blocked = [o for o in ops if o.blocks is not None]
        self._check_divisibility(blocked)
        self._check_mosaic(blocked)
        self._check_index_maps(blocked)
        self._check_dma_indexes()
        self._check_vmem(ops)
        self._check_kernel_body()
        return self.findings

    # --- rule: kernel-grid-divisibility -----------------------------------
    def _check_divisibility(self, blocked: List[_Operand]):
        if not self._want("kernel-grid-divisibility"):
            return
        for op in blocked:
            bad = [(d, a, b) for d, (a, b) in
                   enumerate(zip(op.shape, op.blocks)) if a % b != 0]
            if bad:
                desc = ", ".join(f"dim {d}: {a} % {b} != 0"
                                 for d, a, b in bad)
                self._emit(
                    "kernel-grid-divisibility",
                    f"{self.site.kernel_name}: {op.label} shape "
                    f"{list(op.shape)} is not tiled evenly by block "
                    f"{list(op.blocks)} ({desc}) — the edge block is "
                    "silently padded on read and partially written on "
                    "write; pick a divisor block or pad the operand",
                    operand=op.label, shape=list(op.shape),
                    block=list(op.blocks))

    # --- rule: kernel-mosaic-block ----------------------------------------
    def _check_mosaic(self, blocked: List[_Operand]):
        if not self._want("kernel-mosaic-block"):
            return
        legal = _mosaic_legal()
        for op in blocked:
            bits = _dtype_itemsize(op.dtype) * 8
            try:
                ok = legal(op.blocks, op.shape, dtype_bits=bits)
            except TypeError:  # older signature without dtype_bits
                ok = legal(op.blocks, op.shape)
            if not ok:
                self._emit(
                    "kernel-mosaic-block",
                    f"{self.site.kernel_name}: {op.label} block "
                    f"{list(op.blocks)} over {str(op.dtype)}"
                    f"{list(op.shape)} violates Mosaic tiling for "
                    f"{bits}-bit elements (lane dim % 128, sublane % 8, "
                    "rank-1 % (128 * 32/bits), or exactly the array dim) "
                    "— Mosaic would reject or silently retile this at "
                    "compile time",
                    operand=op.label, block=list(op.blocks),
                    dtype=str(op.dtype))

    # --- rules: kernel-index-oob + kernel-output-coverage -----------------
    def _concrete_scalars(self) -> Optional[tuple]:
        """Concrete numpy values of the scalar-prefetch operands, or None
        when any is traced. Registered verify cases close over an example
        block table (a real ndarray), which makes scalar-driven index
        maps provable: ``table[r, j]`` works on an ndarray exactly as it
        does on the SMEM ref. Traced scalars leave the maps unverifiable
        — skipped and noted, same as any map that raises."""
        import numpy as np
        vals = []
        for o in self.site.scalar_operands:
            try:
                vals.append(np.asarray(o))
            except Exception:  # tracer — no concrete table to prove with
                return None
        return tuple(vals)

    def _eval_map(self, spec, point) -> Optional[Tuple[int, ...]]:
        index_map = getattr(spec, "index_map", None)
        if index_map is None:
            return (0,) * len(spec.block_shape)
        try:
            if self.site.num_scalar_prefetch:
                scalars = self._scalar_args
                if scalars is None:
                    self._index_map_skips.add(
                        "scalar-prefetch operands are traced — index maps "
                        "not provable without a concrete example table")
                    return None
                idx = index_map(*point, *scalars)
            else:
                idx = index_map(*point)
        except Exception as e:  # map needs tracers/refs — skip, note once
            self._index_map_skips.add(f"{type(e).__name__}: {e}")
            return None
        if not isinstance(idx, tuple):
            idx = (idx,)
        try:
            return tuple(int(i) for i in idx)
        except (TypeError, ValueError):
            return None

    def _check_index_maps(self, blocked: List[_Operand]):
        want_oob = self._want("kernel-index-oob")
        want_cov = self._want("kernel-output-coverage")
        if not (want_oob or want_cov) or not self.site.grid:
            return
        self._index_map_skips: set = set()
        self._scalar_args = self._concrete_scalars()
        points, exhaustive = _grid_points(
            self.site.grid, int(self.cfg["index_eval_points"]))
        for op in blocked:
            if not _is_blocked(op.spec):
                continue  # Unblocked specs index in elements — out of scope
            grid_blocks = op.grid_blocks()
            emitted: set = set()
            oob_hit = None
            for point in points:
                idx = self._eval_map(op.spec, point)
                if idx is None or len(idx) != len(grid_blocks):
                    emitted = None
                    break
                emitted.add(idx)
                if oob_hit is None and any(
                        i < 0 or i >= n for i, n in zip(idx, grid_blocks)):
                    oob_hit = (point, idx)
            if oob_hit and want_oob:
                point, idx = oob_hit
                self._emit(
                    "kernel-index-oob",
                    f"{self.site.kernel_name}: {op.label} index_map"
                    f"{tuple(point)} -> block {tuple(idx)} but the valid "
                    f"block range is {tuple(grid_blocks)} for shape "
                    f"{list(op.shape)} / block {list(op.blocks)} — "
                    "out-of-bounds access (off-by-one index_map?)",
                    operand=op.label, grid_point=list(point),
                    block_index=list(idx))
            if (op.role == "out" and want_cov and exhaustive
                    and op.index not in self.site.aliased_outputs
                    and emitted is not None and oob_hit is None):
                required = set(itertools.product(
                    *(range(n) for n in grid_blocks)))
                missing = sorted(required - emitted)
                if missing:
                    preview = ", ".join(str(m) for m in missing[:4])
                    self._emit(
                        "kernel-output-coverage",
                        f"{self.site.kernel_name}: {op.label} — "
                        f"{len(missing)} of {len(required)} output blocks "
                        f"are never written by any grid point (first "
                        f"missing: {preview}) — the uncovered region is "
                        "returned uninitialized",
                        operand=op.label, missing=len(missing),
                        required=len(required))

    def _check_dma_indexes(self):
        """The indexes a kernel hands its own DMA: every entry of a
        declared scalar-prefetch table must be a legal index of the axis
        it addresses (all of them: an unused block-table slot holds the
        null page, which is in range).  Traced tables are not provable."""
        if not self.site.dma_indexes or not self._want("kernel-index-oob"):
            return
        import numpy as np
        scalars = self._concrete_scalars()
        if scalars is None:
            return
        for sc, operand, axis in self.site.dma_indexes:
            if sc >= len(scalars) or operand >= len(self.site.operands):
                continue
            extent = int(self.site.operands[operand].shape[axis])
            table = scalars[sc]
            bad = np.argwhere((table < 0) | (table >= extent))
            if len(bad):
                at = tuple(int(i) for i in bad[0])
                self._emit(
                    "kernel-index-oob",
                    f"{self.site.kernel_name}: scalar operand {sc}{list(at)}"
                    f" = {int(table[at])} indexes axis {axis} of in["
                    f"{operand}] (extent {extent}) in the kernel's own DMA "
                    f"— out-of-bounds HBM read ({len(bad)} such entries)",
                    operand=f"in[{operand}]", scalar_operand=sc,
                    entry=list(at), value=int(table[at]), extent=extent)

    # --- rule: kernel-vmem-budget -----------------------------------------
    def _vmem_budget(self) -> Tuple[int, str]:
        override = self.cfg.get("vmem_budget_bytes")
        if override:
            return int(override), "override"
        budgets = dict(self.cfg["vmem_budgets"])
        kind = ""
        try:
            import jax
            kind = jax.devices()[0].device_kind.lower()
        except Exception:  # no backend at all — fall through to default
            kind = ""
        for gen in sorted(budgets, key=len, reverse=True):
            if gen != "default" and gen in kind:
                return int(budgets[gen]), gen
        return int(budgets.get("default", 12 << 20)), "default"

    def _check_vmem(self, ops: List[_Operand]):
        block_bytes = 0
        for op in ops:
            if _in_hbm(op.spec):
                continue  # the kernel copies out of it; its scratch counts
            dims = op.blocks if op.blocks is not None else op.shape
            block_bytes += math.prod(dims) * _dtype_itemsize(op.dtype)
        scratch_bytes = 0
        for s in self.site.scratch_shapes:
            scratch_bytes += (math.prod(int(d) for d in s.shape)
                              * _dtype_itemsize(s.dtype))
        # scalar-prefetch operands (block tables, per-page scale pools)
        # have no BlockSpec but are resident whole for the kernel's
        # lifetime — a quantized-KV scale pool left out of the estimate
        # would understate the footprint exactly where it grew
        scalar_bytes = 0
        for o in self.site.scalar_operands:
            shape = getattr(o, "shape", None)
            dtype = getattr(o, "dtype", None)
            if shape is None or dtype is None:
                continue
            scalar_bytes += (math.prod(int(d) for d in shape)
                             * _dtype_itemsize(dtype))
        total = block_bytes + scratch_bytes + scalar_bytes
        budget, gen = self._vmem_budget()
        self._record_estimate(block_bytes, scratch_bytes, scalar_bytes,
                              budget, gen)
        if total > budget and self._want("kernel-vmem-budget"):
            self._emit(
                "kernel-vmem-budget",
                f"{self.site.kernel_name}: estimated VMEM footprint "
                f"{total / (1 << 20):.1f} MiB (blocks "
                f"{block_bytes / (1 << 20):.1f} + scratch "
                f"{scratch_bytes / (1 << 20):.1f} + scalar operands "
                f"{scalar_bytes / (1 << 20):.1f}) exceeds the {gen} "
                f"budget of {budget / (1 << 20):.0f} MiB — shrink the "
                "block sizes or stream the large operand "
                "(config key 'vmem_budget_bytes' overrides the budget)",
                vmem_bytes=total, budget_bytes=budget, generation=gen)

    def _record_estimate(self, block_bytes, scratch_bytes, scalar_bytes,
                         budget, gen):
        try:
            from ..profiler import xmem as _xmem
        except ImportError:  # standalone analysis load — no profiler
            return
        _xmem.record_kernel_estimate(
            self.site.kernel_name,
            vmem_bytes=block_bytes + scratch_bytes + scalar_bytes,
            block_bytes=block_bytes, scratch_bytes=scratch_bytes,
            scalar_bytes=scalar_bytes,
            budget_bytes=budget, generation=gen,
            grid=list(self.site.grid),
            where=f"{self.site.file}:{self.site.line}")

    # --- rules: kernel-unused-ref + kernel-narrow-accumulator -------------
    def _kernel_ref_params(self):
        """(fn, positional ref param names) after unwrapping partials."""
        fn = self.site.kernel
        skip_lead = 0
        bound_kw: set = set()
        while isinstance(fn, functools.partial):
            skip_lead += len(fn.args)
            bound_kw |= set(fn.keywords or {})
            fn = fn.func
        try:
            src = textwrap.dedent(inspect.getsource(fn))
            tree = ast.parse(src)
        except (OSError, TypeError, SyntaxError, IndentationError):
            return fn, None, None
        fndef = next((n for n in ast.walk(tree)
                      if isinstance(n, (ast.FunctionDef,
                                        ast.AsyncFunctionDef))), None)
        if fndef is None:
            return fn, None, None
        params = [a.arg for a in fndef.args.args][skip_lead:]
        params = [p for p in params if p not in bound_kw]
        return fn, fndef, params

    def _check_kernel_body(self):
        want_unused = self._want("kernel-unused-ref")
        want_narrow = self._want("kernel-narrow-accumulator")
        if not (want_unused or want_narrow):
            return
        s = self.site
        narrow_in = [_dtype_name(o.dtype) for o in s.operands
                     if _dtype_name(o.dtype) in _NARROW_FLOATS]
        narrow_scratch = [
            (i, _dtype_name(sc.dtype))
            for i, sc in enumerate(s.scratch_shapes)
            if _dtype_name(sc.dtype) in _NARROW_FLOATS]
        if want_narrow and narrow_in and narrow_scratch:
            idx, dt = narrow_scratch[0]
            self._emit(
                "kernel-narrow-accumulator",
                f"{s.kernel_name}: scratch[{idx}] accumulates in {dt} "
                f"over {narrow_in[0]} inputs — rounding error compounds "
                "across the grid; allocate the accumulator as float32 "
                "and cast once on the final write",
                scratch_index=idx, scratch_dtype=dt)
        if not want_unused:
            return
        fn, fndef, params = self._kernel_ref_params()
        if fndef is None or params is None:
            return
        n_in, n_out = len(s.operands), len(s.out_shapes)
        n_scratch = len(s.scratch_shapes)
        nsp = s.num_scalar_prefetch
        if len(params) < nsp + n_in + n_out:
            return  # signature does not line up (varargs etc.) — skip
        roles = ([("scalar", i) for i in range(nsp)]
                 + [("in", i) for i in range(n_in)]
                 + [("out", i) for i in range(n_out)]
                 + [("scratch", i) for i in range(n_scratch)])
        used = {n.id for stmt in fndef.body for n in ast.walk(stmt)
                if isinstance(n, ast.Name)}
        file = None
        try:
            file = inspect.getsourcefile(fn)
        except TypeError:
            file = None
        line = getattr(getattr(fn, "__code__", None), "co_firstlineno",
                       None)
        for pname, (role, i) in zip(params, roles):
            if role in ("in", "scalar") or pname in used \
                    or pname.startswith("_"):
                continue
            self._emit(
                "kernel-unused-ref",
                f"{s.kernel_name}: {role} ref '{pname}' "
                f"({role}[{i}]) is never referenced in the kernel body "
                "— it still occupies VMEM every invocation; drop it or "
                "prefix it with '_' if intentionally reserved",
                file=file, line=line, ref=pname, role=role)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def check_sites(sites: Iterable[KernelSite], name: Optional[str] = None,
                config: Optional[dict] = None, rules=None) -> List[Finding]:
    """Run every kernel rule over captured sites (pragmas in the
    attributed files are honored, same as the other levels)."""
    cfg = dict(DEFAULT_KERNEL_CONFIG)
    if config:
        cfg.update(config)
    out: List[Finding] = []
    for site in sites:
        out.extend(_SiteChecker(site, cfg, name, rules).run())
    return _core.filter_file_pragmas(out)


def verify_kernel(fn: Callable, *avals, name: Optional[str] = None,
                  config: Optional[dict] = None, rules=None
                  ) -> List[Finding]:
    """Abstractly evaluate ``fn(*avals)`` (ShapeDtypeStructs or arrays —
    nothing executes, no TPU needed) and verify every ``pl.pallas_call``
    it traces.  Returns the findings; empty means the kernel(s) proved
    clean under the exhaustive-grid rules and heuristically clean under
    the rest."""
    import jax
    sites: List[KernelSite] = []
    with capture_sites(sites):
        # a fresh wrapper per call defeats the jit trace cache —
        # eval_shape on a previously-traced (fn, avals) pair would
        # replay the cached jaxpr and never reach the pallas_call shim
        jax.eval_shape(lambda *a: fn(*a), *avals)
    return check_sites(
        sites, config=config, rules=rules,
        name=name or getattr(fn, "__qualname__",
                             getattr(fn, "__name__", repr(fn))))


# ---------------------------------------------------------------------------
# the kernel registry: ops modules register providers at import time so
# the CLI / tier-1 ratchet can sweep every shipped kernel.
# ---------------------------------------------------------------------------

_CASES: List[tuple] = []            # (case_name, fn, avals)
_PROVIDERS: Dict[str, Callable] = {}  # provider name -> () -> [cases]


def register_kernel_case(name: str, fn: Callable, avals: tuple) -> None:
    """Register one (name, traceable fn, example avals) case directly."""
    _CASES.append((name, fn, tuple(avals)))


def register_kernel_provider(name: str, provider: Callable) -> None:
    """Register a lazy case provider (called only when a sweep runs) —
    the import-time hook ops/pallas_ops.py uses."""
    _PROVIDERS[name] = provider


def registered_cases() -> List[tuple]:
    """All registered cases, importing the built-in kernel library first
    so its import-time registration has happened."""
    try:
        import importlib
        importlib.import_module("paddle_tpu.ops.pallas_ops")
    except ImportError:  # standalone / jax-free environment
        importlib = None
    cases = list(_CASES)
    providers = dict(_PROVIDERS)
    # When this module was loaded standalone (the CLI's
    # "tpu_lint_analysis" alias), import-time registration from
    # pallas_ops landed in the canonical package module — merge it.
    canon = sys.modules.get("paddle_tpu.analysis.kernel_checks")
    if canon is not None and canon.__dict__ is not globals():
        cases.extend(getattr(canon, "_CASES", []))
        providers.update(getattr(canon, "_PROVIDERS", {}))
    for pname in sorted(providers):
        cases.extend(providers[pname]())
    return cases


def verify_registered(names=None, config: Optional[dict] = None,
                      rules=None) -> List[Finding]:
    """Sweep every registered kernel case through :func:`verify_kernel`.
    A case that fails to even trace becomes a ``kernel-verifier-error``
    finding rather than an exception — the sweep always completes."""
    out: List[Finding] = []
    for case_name, fn, avals in registered_cases():
        if names is not None and case_name not in names:
            continue
        try:
            out.extend(verify_kernel(fn, *avals, name=case_name,
                                     config=config, rules=rules))
        except Exception as e:
            out.append(Finding(
                rule="kernel-verifier-error", severity=WARNING,
                message=f"kernel case '{case_name}' failed to trace: "
                        f"{type(e).__name__}: {e}",
                function=case_name, source="kernel",
                extra={"case": case_name}))
    return out


def verify_module(path: str, config: Optional[dict] = None,
                  rules=None) -> Tuple[List[Finding], int]:
    """Load a python file and verify the cases its
    ``kernel_verify_cases()`` hook returns.  Used by the CLI
    ``--kernels`` mode for out-of-tree kernel modules.  Returns
    (findings, number of cases run)."""
    import importlib
    import importlib.util
    import os
    # A file inside a package (``__init__.py`` parents) must be imported
    # under its dotted name or its relative imports break; walk up to
    # find the package root, then import normally.
    apath = os.path.abspath(path)
    parts = [os.path.basename(apath)[:-3] if apath.endswith(".py")
             else os.path.basename(apath)]
    pkg_dir = os.path.dirname(apath)
    while os.path.isfile(os.path.join(pkg_dir, "__init__.py")):
        parts.insert(0, os.path.basename(pkg_dir))
        pkg_dir = os.path.dirname(pkg_dir)
    if len(parts) > 1:
        if pkg_dir not in sys.path:
            sys.path.insert(0, pkg_dir)
        mod = importlib.import_module(".".join(parts))
    else:
        modname = "_tpu_lint_kernels_" + parts[0]
        spec = importlib.util.spec_from_file_location(modname, apath)
        if spec is None or spec.loader is None:
            raise ImportError(f"cannot load {path}")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    hook = getattr(mod, "kernel_verify_cases", None)
    if hook is None:
        return [], 0
    out: List[Finding] = []
    cases = list(hook())
    for case_name, fn, avals in cases:
        try:
            out.extend(verify_kernel(fn, *avals, name=case_name,
                                     config=config, rules=rules))
        except Exception as e:
            out.append(Finding(
                rule="kernel-verifier-error", severity=WARNING,
                message=f"kernel case '{case_name}' failed to trace: "
                        f"{type(e).__name__}: {e}",
                file=path, function=case_name, source="kernel",
                extra={"case": case_name}))
    return out, len(cases)
