"""Parallel environment bootstrap + DataParallel.

Reference analog: python/paddle/distributed/parallel.py:318
(init_parallel_env: reads PADDLE_* env from the launcher, TCPStore
rendezvous, ProcessGroup creation) and python/paddle/fluid/dygraph/
parallel.py (DataParallel + EagerReducer grad bucketing).

TPU-native: multi-host bootstrap is jax.distributed.initialize (the
TCPStore/launcher analog); within a host all chips are addressable, so
"one process per device" becomes "one process per host". DataParallel is a
thin wrapper: gradients are averaged by `pmean` inside the compiled step
(GSPMD inserts it from batch sharding), so the EagerReducer's bucketing/
overlap machinery is unnecessary by construction — XLA overlaps the
all-reduce with backward compute during scheduling (SURVEY.md §2.5 item 9).
"""
from __future__ import annotations

import os
from typing import Optional

import jax

from ..core.tensor import Tensor
from ..nn.layer.layers import Layer
from .mesh import init_mesh, get_topology
from .collective import all_reduce, get_rank, get_world_size

__all__ = ["init_parallel_env", "shutdown", "ParallelEnv", "DataParallel",
           "get_rank", "get_world_size"]

_INITIALIZED = [False]


def shutdown():
    """Tear down the multi-process gang so a worker can exit 0 through
    NORMAL interpreter shutdown — the inverse of init_parallel_env.

    Reference analog: ProcessGroup destruction + tcp_store shutdown at
    trainer exit. The jax coordination service orders the teardown
    internally (its shutdown barrier holds the coordinator open until
    every client has disconnected), so after this returns ``sys.exit(0)``
    is safe; no ``os._exit`` escape hatch is needed. Idempotent, and
    also works when the gang was bootstrapped with raw
    ``jax.distributed.initialize`` instead of init_parallel_env.
    """
    _INITIALIZED[0] = False
    if jax.distributed.is_initialized():
        jax.distributed.shutdown()


def init_parallel_env(strategy=None):
    """Bootstrap multi-host jax.distributed from PADDLE_*/standard envs."""
    if _INITIALIZED[0]:
        return ParallelEnv()
    coord = os.environ.get("PADDLE_MASTER",
                           os.environ.get("MASTER_ADDR"))
    nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM",
                                os.environ.get("WORLD_SIZE", "1")))
    pid = int(os.environ.get("PADDLE_TRAINER_ID",
                             os.environ.get("RANK", "0")))
    if nprocs > 1 and coord:
        port = os.environ.get("MASTER_PORT", "8476")
        jax.distributed.initialize(
            coordinator_address=f"{coord}:{port}"
            if ":" not in coord else coord,
            num_processes=nprocs, process_id=pid)
    if get_topology() is None:
        init_mesh()
    _INITIALIZED[0] = True
    return ParallelEnv()


class ParallelEnv:
    """reference: python/paddle/fluid/dygraph/parallel.py ParallelEnv."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def local_rank(self):
        return int(os.environ.get("PADDLE_LOCAL_RANK", "0"))

    @property
    def device_id(self):
        return self.local_rank

    @property
    def nranks(self):
        return self.world_size

    @property
    def dev_id(self):
        return self.local_rank

    @property
    def trainer_endpoints(self):
        return os.environ.get("PADDLE_TRAINER_ENDPOINTS", "").split(",")

    @property
    def current_endpoint(self):
        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "")


class DataParallel(Layer):
    """Wrapper for dygraph DP parity.

    Under the TPU execution model the wrapped forward is unchanged; what
    makes it data-parallel is (a) feeding batch-sharded arrays (see
    distributed.shard_batch / DistributedBatchSampler) and (b) running the
    step under jit with the global mesh, where XLA turns the parameter
    gradients into psums over the 'dp' axis. For eager single-host use with
    explicit multi-device grads, `apply_collective_grads` mirrors the
    reference's fused allreduce hook.
    """

    def __init__(self, layers, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group=None):
        super().__init__()
        self._layers = layers
        self.find_unused_parameters = find_unused_parameters

    def forward(self, *inputs, **kwargs):
        return self._layers(*inputs, **kwargs)

    def state_dict(self, *args, **kwargs):
        return self._layers.state_dict(*args, **kwargs)

    def set_state_dict(self, state_dict, *args, **kwargs):
        return self._layers.set_state_dict(state_dict, *args, **kwargs)

    def apply_collective_grads(self):
        for p in self._layers.parameters():
            if p.grad is not None:
                all_reduce(p.grad, op="avg")

    def scale_loss(self, loss):
        return loss

    @property
    def parameters_(self):
        return self._layers.parameters()
