"""Collective communication API.

Reference analog: python/paddle/distributed/collective.py +
communication/ (all_reduce/all_gather/... over ProcessGroupNCCL,
paddle/fluid/distributed/collective/process_group.h:53).

TPU-native: collectives are XLA ops (lax.psum / all_gather / ppermute /
all_to_all) over named mesh axes. Two modes:

1. **Traced** (inside shard_map/pjit): the functions below call the lax
   collective directly — this is the hot path, compiled onto ICI.
2. **Eager facade**: outside a trace there is nothing to communicate with
   on a single process; the ops are the mathematical identity for
   world_size==1 (matching the reference's behavior for a 1-rank group)
   and raise for multi-host eager use, which the reference also routes
   through compiled programs in practice.

Groups: a `Group` names a mesh axis (or tuple of axes) — the ring-id
analog.
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.tensor import Tensor, apply_op
from ..testing.chaos import chaos_point
from .mesh import get_mesh

__all__ = ["ReduceOp", "Group", "new_group", "get_group", "all_reduce",
           "all_gather", "all_gather_object", "broadcast", "reduce",
           "scatter", "alltoall", "all_to_all", "send", "recv", "reduce_scatter",
           "barrier", "get_rank", "get_world_size", "is_initialized",
           "destroy_process_group", "wait", "stream"]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """Names one or more mesh axes (the process-group analog)."""

    def __init__(self, axis="dp", ranks=None, gid=0):
        self.axis = axis
        self.ranks = ranks
        self.id = gid

    @property
    def nranks(self):
        mesh = get_mesh()
        if mesh is None:
            return 1
        ax = self.axis
        if isinstance(ax, (tuple, list)):
            return int(np.prod([mesh.shape[a] for a in ax]))
        return mesh.shape.get(ax, 1)

    @property
    def world_size(self):
        return self.nranks

    def __repr__(self):
        return f"Group(axis={self.axis})"


_GROUPS = {0: Group("dp", gid=0)}
_NEXT_GID = [1]


def new_group(ranks=None, backend=None, timeout=None, axis=None):
    gid = _NEXT_GID[0]
    _NEXT_GID[0] += 1
    g = Group(axis or "dp", ranks, gid)
    _GROUPS[gid] = g
    return g


def get_group(gid=0):
    return _GROUPS.get(gid)


def get_rank(group=None):
    import os
    return int(os.environ.get("PADDLE_TRAINER_ID",
                              jax.process_index()))


def get_world_size(group=None):
    if group is not None:
        return group.nranks
    import os
    return int(os.environ.get("PADDLE_TRAINERS_NUM", jax.process_count()))


def is_initialized():
    return True


def destroy_process_group(group=None):
    """Destroying the global group tears down the gang (reference:
    collective.destroy_process_group); named sub-groups are views over
    the mesh with nothing to free."""
    if group is None:
        from .parallel import shutdown
        shutdown()


def barrier(group=None):
    jnp.zeros(()).block_until_ready()


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor) and not isinstance(
            tensor._array, jax.core.Tracer):
        tensor._array.block_until_ready()


def _axis_of(group):
    if group is None:
        return "dp"
    if isinstance(group, Group):
        return group.axis
    if isinstance(group, str):
        return group
    return "dp"


def _in_trace(x):
    return isinstance(x, jax.core.Tracer)


# ---------------------------------------------------------------------------
# collectives — lax under trace, identity on 1-rank eager
# ---------------------------------------------------------------------------

def _apply_collective(f, tensor, op_name):
    """apply_op with telemetry and health instrumentation: a
    ``collective/<op>`` span (``profiler.trace.span``: a profiler
    annotation, and the flight recorder's event under
    FLAGS_tpu_trace); when FLAGS_tpu_metrics is on, bytes-moved
    counters + a latency histogram per collective op; when a runtime
    HealthMonitor is installed, an entry/exit beacon (so a rank that
    enters and never exits is detected within the collective deadline)
    plus a ``collective.<op>`` chaos point for hang injection. The
    un-instrumented path costs the inert annotation, two
    dict-lookup+bools (trace, metrics), and two module-global None
    checks (health hook, chaos hook)."""
    from ..profiler import metrics as _metrics, trace as _trace
    from ..runtime import health as _health
    rec = _metrics.enabled()
    t0 = time.perf_counter() if rec else None
    span_name = f"collective/{op_name}"
    # the health beacon promoted to a first-class span: a profiler
    # annotation always and, when FLAGS_tpu_trace is on, the flight
    # recorder's event with its duration
    with _trace.span(span_name):
        # beacon outermost: the chaos hang below must count as "inside
        # the collective" so self-detection sees the overdue beacon
        with _health.collective_beacon(op_name):
            chaos_point(f"collective.{op_name}",
                        step=_health.current_step())
            out = apply_op(f, tensor, op_name=op_name)
    if rec:
        a = getattr(tensor, "_array", tensor)
        try:
            nbytes = int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
        except Exception:
            nbytes = 0
        _metrics.counter("collective_calls_total",
                         "Collective invocations", op=op_name).inc()
        _metrics.counter("collective_bytes_total",
                         "Input bytes handed to collectives",
                         op=op_name).inc(nbytes)
        _metrics.histogram("collective_latency_seconds",
                           "Host wall time per collective call (trace "
                           "time under jit/shard_map)",
                           op=op_name).observe(time.perf_counter() - t0)
    return out


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    axis = _axis_of(group)

    def _f(a):
        if not _in_trace(a):
            return a  # single-process eager: group of size 1
        if op in (ReduceOp.SUM, "sum"):
            return lax.psum(a, axis)
        if op in (ReduceOp.MAX, "max"):
            return lax.pmax(a, axis)
        if op in (ReduceOp.MIN, "min"):
            return lax.pmin(a, axis)
        if op in (ReduceOp.AVG, "avg"):
            return lax.pmean(a, axis)
        if op in (ReduceOp.PROD, "prod"):
            # sign/magnitude decomposition: log/exp alone breaks on
            # zeros and negatives
            mag = jnp.exp(lax.psum(jnp.log(jnp.maximum(jnp.abs(a), 1e-38)),
                                   axis))
            neg = lax.psum((a < 0).astype(jnp.int32), axis)
            has_zero = lax.pmax((a == 0).astype(jnp.int32), axis)
            sign = jnp.where(neg % 2 == 1, -1.0, 1.0).astype(a.dtype)
            return jnp.where(has_zero == 1, jnp.zeros_like(mag),
                             sign * mag.astype(a.dtype))
        raise ValueError(f"unknown op {op}")
    out = _apply_collective(_f, tensor, "all_reduce")
    tensor._set_array(out._array)
    return tensor


def all_gather(tensor_list, tensor=None, group=None, sync_op=True, axis=0):
    """paddle signature: all_gather(tensor_list, tensor). Traced form:
    pass tensor only, returns the gathered Tensor."""
    if tensor is None:
        tensor, tensor_list = tensor_list, None
    ax_name = _axis_of(group)

    def _f(a):
        if not _in_trace(a):
            return a[None] if tensor_list is not None else a
        return lax.all_gather(a, ax_name, axis=0)
    out = _apply_collective(_f, tensor, "all_gather")
    if tensor_list is not None:
        n = out.shape[0]
        from ..tensor.manipulation import unstack
        parts = unstack(out, axis=0)
        tensor_list.clear()
        tensor_list.extend(parts)
        return tensor_list
    return out


def all_gather_object(object_list, obj, group=None):
    object_list.clear()
    object_list.append(obj)
    return object_list


def broadcast(tensor, src=0, group=None, sync_op=True):
    axis = _axis_of(group)

    def _f(a):
        if not _in_trace(a):
            return a
        # broadcast = select src's value: gather then index (XLA folds this)
        gathered = lax.all_gather(a, axis, axis=0)
        return gathered[src]
    out = _apply_collective(_f, tensor, "broadcast")
    tensor._set_array(out._array)
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # On SPMD hardware reduce == all_reduce with result used on dst.
    return all_reduce(tensor, op, group, sync_op)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    axis = _axis_of(group)
    if tensor_list is not None and not _in_trace(tensor._array):
        tensor._set_array(tensor_list[get_rank(group)]._array)
        return tensor

    def _f(a):
        if not _in_trace(a):
            return a
        idx = lax.axis_index(axis)
        n = lax.axis_size(axis)
        chunk = a.shape[0] // n
        return lax.dynamic_slice_in_dim(a, idx * chunk, chunk, axis=0)
    out = _apply_collective(_f, tensor, "scatter")
    tensor._set_array(out._array)
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    """Traced form: pass a single stacked Tensor [n_ranks, ...] and get the
    transposed-exchange result (the MoE dispatch primitive,
    reference: global_scatter_op.cc)."""
    axis = _axis_of(group)
    if isinstance(in_tensor_list, (list, tuple)):
        from ..tensor.manipulation import stack, unstack
        stacked = stack(list(in_tensor_list), axis=0)
        out = alltoall(stacked, None, group, sync_op)
        parts = unstack(out, axis=0)
        if out_tensor_list is not None:
            out_tensor_list.clear()
            out_tensor_list.extend(parts)
            return out_tensor_list
        return parts

    def _f(a):
        if not _in_trace(a):
            return a
        return lax.all_to_all(a, axis, split_axis=0, concat_axis=0,
                              tiled=False)
    return _apply_collective(_f, in_tensor_list, "alltoall")


all_to_all = alltoall


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    axis = _axis_of(group)
    if tensor_list is not None and not _in_trace(tensor._array):
        from ..tensor.math import add_n
        tensor._set_array(add_n(list(tensor_list))._array)
        return tensor

    def _f(a):
        if not _in_trace(a):
            return a
        return lax.psum_scatter(a, axis, scatter_dimension=0, tiled=True)
    out = _apply_collective(_f, tensor, "reduce_scatter")
    return out


def send(tensor, dst=0, group=None, sync_op=True):
    """p2p send — traced form is a ppermute shift (PP pipelines use
    distributed.pipeline's ppermute helpers directly)."""
    axis = _axis_of(group)

    def _f(a):
        if not _in_trace(a):
            return a
        n = lax.axis_size(axis)
        perm = [(i, (i + 1) % n) for i in range(n)]
        return lax.ppermute(a, axis, perm)
    return _apply_collective(_f, tensor, "send")


def recv(tensor, src=0, group=None, sync_op=True):
    return tensor


class stream:
    """paddle.distributed.communication.stream parity — on XLA there is one
    logical stream; these re-export the sync collectives."""
    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    broadcast = staticmethod(broadcast)
    reduce = staticmethod(reduce)
    scatter = staticmethod(scatter)
    alltoall = staticmethod(alltoall)
    reduce_scatter = staticmethod(reduce_scatter)
    send = staticmethod(send)
    recv = staticmethod(recv)


def alltoall_single(in_tensor, out_tensor=None, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    """Single-tensor all-to-all: leading dim split across ranks
    (reference: python/paddle/distributed/communication/all_to_all.py
    alltoall_single). Equal splits only — unequal splits have no static
    shape and do not map to XLA collectives."""
    assert in_split_sizes is None and out_split_sizes is None, \
        "alltoall_single: only equal splits are supported on XLA " \
        "(unequal splits are not static-shape compatible)"
    axis = _axis_of(group)

    def _f(a):
        if not _in_trace(a):
            return a
        return lax.all_to_all(a, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    out = _apply_collective(_f, in_tensor, "alltoall_single")
    if out_tensor is not None:
        out_tensor._set_array(out._array)
        return out_tensor
    return out


class _CompletedTask:
    """Future-like handle for the isend/irecv API (XLA collectives are
    scheduled by the compiler; by the time python sees the result it is
    already ordered — reference: communication/batch_isend_irecv.py
    P2POp task semantics)."""

    def wait(self):
        return True

    def is_completed(self):
        return True


def isend(tensor, dst=0, group=None):
    """reference: communication/send.py isend — returns a task."""
    send(tensor, dst, group)
    return _CompletedTask()


def irecv(tensor, src=0, group=None):
    """reference: communication/recv.py irecv."""
    recv(tensor, src, group)
    return _CompletedTask()


def get_backend(group=None):
    """reference: collective.py get_backend — the one backend here is XLA
    collectives over ICI/DCN."""
    return "XCCL"


def broadcast_object_list(object_list, src=0, group=None):
    """reference: broadcast_object_list — single-process eager facade:
    src's objects are already the local list (world of 1); multi-host
    object broadcast rides the TCPStore (store.set/wait) in the gang
    scripts."""
    return object_list


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    """reference: scatter_object_list — world-of-1 facade: rank 0 keeps
    its slice."""
    if in_object_list:
        out_object_list.clear()
        out_object_list.append(in_object_list[get_rank(group) %
                                              len(in_object_list)])
    return out_object_list


def gloo_barrier():
    """reference: gloo_barrier — CPU-side barrier; maps to the device
    barrier (single-process) / store barrier in gang scripts."""
    barrier()


def gloo_release():
    """reference: gloo_release — nothing to free on this stack."""
