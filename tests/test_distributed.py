"""Distributed tests on the 8-device virtual CPU mesh.

Mirrors the reference's single-host multi-process distributed tests
(SURVEY.md §4 TestDistBase) — here multi-device single-process, which is
the TPU execution model.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    dist.mesh._GLOBAL_MESH[0] = None
    dist.mesh._GLOBAL_TOPO[0] = None


def test_eight_devices_available():
    assert jax.device_count() >= 8


class TestMesh:
    def test_init_mesh_shapes(self):
        topo = dist.init_mesh(dp=2, mp=4)
        assert topo.world_size() == 8
        assert topo.mesh.shape["dp"] == 2
        assert topo.mesh.shape["mp"] == 4

    def test_default_pure_dp(self):
        topo = dist.init_mesh()
        assert topo.dp_degree == 8

    def test_process_mesh(self):
        pm = dist.ProcessMesh([[0, 1, 2, 3], [4, 5, 6, 7]],
                              dim_names=["x", "y"])
        assert pm.shape == [2, 4]
        m = pm.to_jax_mesh()
        assert m.shape["x"] == 2 and m.shape["y"] == 4


class TestShardTensor:
    def test_shard_and_replicate(self):
        topo = dist.init_mesh(dp=8)
        x = paddle.randn([16, 4])
        dist.shard_tensor(x, placements=P("dp", None))
        assert len(x._array.sharding.device_set) == 8
        y = paddle.randn([4])
        dist.shard_tensor(y, placements=P())
        assert y._array.sharding.is_fully_replicated

    def test_shard_params(self):
        topo = dist.init_mesh(mp=8)
        layer = dist.fleet.ColumnParallelLinear(16, 32, gather_output=False)
        dist.shard_params(layer)
        assert not layer.weight._array.sharding.is_fully_replicated


class TestCollectivesUnderShardMap:
    def test_all_reduce_psum(self):
        topo = dist.init_mesh(dp=8)
        from jax import shard_map

        def f(x):
            t = paddle.Tensor(x, stop_gradient=True)
            out = dist.all_reduce(t, group=dist.Group("dp"))
            return out._array

        xs = jnp.arange(8.0).reshape(8, 1)
        out = shard_map(f, mesh=topo.mesh, in_specs=P("dp", None),
                        out_specs=P("dp", None))(xs)
        np.testing.assert_allclose(np.asarray(out),
                                   np.full((8, 1), 28.0))

    def test_all_gather(self):
        topo = dist.init_mesh(dp=8)
        from jax import shard_map

        def f(x):
            t = paddle.Tensor(x, stop_gradient=True)
            return dist.all_gather(t, group=dist.Group("dp"))._array

        xs = jnp.arange(8.0).reshape(8, 1)
        out = shard_map(f, mesh=topo.mesh, in_specs=P("dp", None),
                        out_specs=P("dp", None, None))(xs)
        # every shard holds the full gathered vector
        np.testing.assert_allclose(np.asarray(out).reshape(8, 8, 1)[0, :, 0],
                                   np.arange(8.0))

    def test_all_to_all(self):
        topo = dist.init_mesh(dp=8)
        from jax import shard_map

        def f(x):
            t = paddle.Tensor(x, stop_gradient=True)
            return dist.alltoall(t, group=dist.Group("dp"))._array

        # each device holds [8,1] — row j goes to device j
        xs = jnp.arange(64.0).reshape(64, 1)
        out = shard_map(f, mesh=topo.mesh, in_specs=P("dp", None),
                        out_specs=P("dp", None))(xs)
        ref = np.arange(64.0).reshape(8, 8).T.reshape(64, 1)
        np.testing.assert_allclose(np.asarray(out), ref)

    def test_reduce_scatter(self):
        topo = dist.init_mesh(dp=8)
        from jax import shard_map

        def f(x):
            t = paddle.Tensor(x, stop_gradient=True)
            return dist.reduce_scatter(t, group=dist.Group("dp"))._array

        xs = jnp.ones((64, 8))
        out = shard_map(f, mesh=topo.mesh, in_specs=P("dp", None),
                       out_specs=P("dp", None))(xs)
        np.testing.assert_allclose(np.asarray(out), np.full((8, 8), 8.0))

    def test_collective_latency_histogram_populates(self):
        """With FLAGS_tpu_metrics on, every collective records a latency
        observation and bytes-moved counter (profiler/metrics.py) — the
        serving-paper telemetry for spotting a slow ICI link without
        attaching xprof."""
        from paddle_tpu.profiler import metrics
        metrics.reset()
        paddle.set_flags({"FLAGS_tpu_metrics": True})
        try:
            topo = dist.init_mesh(dp=8)
            from jax import shard_map

            def f(x):
                t = paddle.Tensor(x, stop_gradient=True)
                return dist.all_reduce(t, group=dist.Group("dp"))._array

            xs = jnp.arange(8.0).reshape(8, 1)
            shard_map(f, mesh=topo.mesh, in_specs=P("dp", None),
                      out_specs=P("dp", None))(xs)
            snap = metrics.snapshot()
            hist = snap['collective_latency_seconds{op="all_reduce"}']
            assert hist["count"] >= 1
            assert hist["sum"] > 0
            assert snap['collective_calls_total{op="all_reduce"}'] >= 1
            # one [1]-float32 shard per device enters the trace: 4 bytes
            assert snap['collective_bytes_total{op="all_reduce"}'] >= 4
        finally:
            paddle.set_flags({"FLAGS_tpu_metrics": False})
            metrics.reset()


class TestDataParallelTraining:
    def test_dp_sharded_step_matches_single(self):
        """Loss/grads identical whether batch is sharded over 8 devices or
        not — the EagerReducer parity check (SURVEY.md §2.5 item 9)."""
        paddle.seed(3)
        topo = dist.init_mesh(dp=8)
        net = nn.Linear(4, 2)
        x_np = np.random.randn(16, 4).astype("float32")
        y_np = np.random.randint(0, 2, (16,)).astype("int32")

        def loss_fn(x, y):
            return F.cross_entropy(net(paddle.Tensor(x, stop_gradient=True)),
                                   paddle.Tensor(y))

        # single-device
        loss1 = loss_fn(jnp.asarray(x_np), jnp.asarray(y_np))
        loss1.backward()
        g1 = net.weight.grad.numpy().copy()
        net.clear_gradients()

        # batch sharded over dp under jit
        xs = jax.device_put(jnp.asarray(x_np),
                            NamedSharding(topo.mesh, P("dp", None)))
        ys = jax.device_put(jnp.asarray(y_np),
                            NamedSharding(topo.mesh, P("dp")))
        params = net.parameters()

        def step(raw, x, y):
            for p, a in zip(params, raw):
                p._set_array(a)
                p.grad = None
                p._node = None
            loss = loss_fn(x, y)
            loss.backward()
            return loss._array, [p.grad._array for p in params]

        with topo.mesh:
            loss2, grads2 = jax.jit(step)([p._array for p in params], xs, ys)
        np.testing.assert_allclose(float(loss1.item()), float(loss2),
                                   rtol=1e-5)
        np.testing.assert_allclose(g1, np.asarray(grads2[0]), atol=1e-5)


class TestTensorParallel:
    def test_column_row_parallel_matches_serial(self):
        """TP layers under the mesh produce the same math as dense layers
        (mp_layers.py parity)."""
        paddle.seed(5)
        topo = dist.init_mesh(mp=8)
        col = dist.fleet.ColumnParallelLinear(16, 32, gather_output=False)
        row = dist.fleet.RowParallelLinear(32, 16, input_is_parallel=True)
        dist.shard_params(col)
        dist.shard_params(row)

        x_np = np.random.randn(4, 16).astype("float32")

        def fwd(x):
            t = paddle.Tensor(x, stop_gradient=True)
            return row(col(t))._array

        with topo.mesh:
            out = jax.jit(fwd)(jnp.asarray(x_np))
        ref = (x_np @ col.weight.numpy() + col.bias.numpy()) \
            @ row.weight.numpy() + row.bias.numpy()
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4)

    def test_vocab_parallel_embedding(self):
        topo = dist.init_mesh(mp=8)
        emb = dist.fleet.VocabParallelEmbedding(64, 16)
        dist.shard_params(emb)
        ids = np.array([[0, 5], [63, 32]], dtype="int32")

        def fwd(i):
            return emb(paddle.Tensor(i, stop_gradient=True))._array

        with topo.mesh:
            out = jax.jit(fwd)(jnp.asarray(ids))
        np.testing.assert_allclose(np.asarray(out),
                                   emb.weight.numpy()[ids], atol=1e-5)

    def test_tp_training_step_grads(self):
        paddle.seed(9)
        topo = dist.init_mesh(dp=2, mp=4)
        col = dist.fleet.ColumnParallelLinear(8, 16, gather_output=False)
        row = dist.fleet.RowParallelLinear(16, 8, input_is_parallel=True)
        dist.shard_params(col)
        dist.shard_params(row)
        params = list(col.parameters()) + list(row.parameters())
        x_np = np.random.randn(4, 8).astype("float32")

        def step(raw, x):
            for p, a in zip(params, raw):
                p._set_array(a)
                p.grad = None
                p._node = None
            out = row(col(paddle.Tensor(x, stop_gradient=True)))
            loss = paddle.sum(out * out)
            loss.backward()
            return loss._array, [p.grad._array for p in params]

        raw0 = [p._array for p in params]
        with topo.mesh:
            loss, grads = jax.jit(step)(raw0, jnp.asarray(x_np))
        # reference grads computed densely without mesh; restore real arrays
        # (tracing leaves tracers in p._array)
        dist.mesh._GLOBAL_MESH[0] = None
        for p, a in zip(params, raw0):
            p._set_array(a)
            p.grad = None
            p._node = None
        out = row(col(paddle.to_tensor(x_np)))
        ref_loss = paddle.sum(out * out)
        ref_loss.backward()
        np.testing.assert_allclose(float(loss), ref_loss.item(), rtol=1e-4)
        for p, g in zip(params, grads):
            np.testing.assert_allclose(p.grad.numpy(), np.asarray(g),
                                       atol=2e-3, rtol=1e-3)


class TestSharding:
    def test_zero_spec(self):
        topo = dist.init_mesh(sharding=8)
        from paddle_tpu.distributed.sharding import zero_spec_for_param
        p = nn.Parameter(np.zeros((64, 32), dtype="float32"))
        spec = zero_spec_for_param(p)
        assert "sharding" in spec

    def test_group_sharded_annotations(self):
        topo = dist.init_mesh(sharding=8)
        net = nn.Linear(64, 64)
        opt = paddle.optimizer.AdamW(parameters=net.parameters(),
                                     learning_rate=1e-3)
        net2, opt2, _ = dist.sharding.group_sharded_parallel(net, opt,
                                                             "p_g_os")
        assert getattr(net2.weight, "opt_state_spec", None) is not None


class TestFleet:
    def test_fleet_init(self):
        strategy = dist.fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                                   "pp_degree": 2, "sharding_degree": 1}
        topo = dist.fleet.init(is_collective=True, strategy=strategy)
        assert topo.world_size() == 8
        hcg = dist.fleet.get_hybrid_communicate_group()
        assert hcg.mp_degree == 2 and hcg.pp_degree == 2

    def test_rng_tracker(self):
        from paddle_tpu.distributed.random import (get_rng_state_tracker,
                                                   model_parallel_random_seed)
        model_parallel_random_seed(1234)
        tracker = get_rng_state_tracker()
        with tracker.rng_state():
            a = paddle.randn([4])
        with tracker.rng_state():
            b = paddle.randn([4])
        assert not np.allclose(a.numpy(), b.numpy())


def test_gradient_merge_optimizer():
    """k-step gradient merge: parity with a k-times-larger batch
    (reference: fleet/meta_optimizers/gradient_merge_optimizer.py)."""
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import fleet

    rng = np.random.default_rng(0)
    X = rng.standard_normal((8, 4)).astype(np.float32)
    Y = rng.standard_normal((8, 1)).astype(np.float32)

    def train(k_steps, micro):
        paddle.seed(0)
        net = nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        strategy = fleet.DistributedStrategy()
        if k_steps > 1:
            strategy.gradient_merge = True
            strategy.gradient_merge_configs = {"k_steps": k_steps,
                                               "avg": True}
        opt = fleet.distributed_optimizer(opt, strategy)
        for start in range(0, 8, micro):
            xb = paddle.to_tensor(X[start:start + micro])
            yb = paddle.to_tensor(Y[start:start + micro])
            loss = nn.functional.mse_loss(net(xb), yb)
            loss.backward()
            opt.step()
            opt.clear_grad()
        return net.weight.numpy()

    # 2 micro-steps of 4 merged == 1 full-batch step of 8
    merged = train(k_steps=2, micro=4)
    full = train(k_steps=1, micro=8)
    np.testing.assert_allclose(merged, full, rtol=1e-5, atol=1e-6)

    # state roundtrip preserves the mid-accumulation counter
    from paddle_tpu.distributed.fleet.gradient_merge import (
        GradientMergeOptimizer)
    paddle.seed(0)
    net = nn.Linear(4, 1)
    gm = GradientMergeOptimizer(
        paddle.optimizer.SGD(learning_rate=0.1,
                             parameters=net.parameters()), k_steps=2)
    loss = nn.functional.mse_loss(net(paddle.to_tensor(X)),
                                  paddle.to_tensor(Y))
    loss.backward()
    gm.step()  # 1 of 2: inner must not have applied yet
    # mid-accumulation checkpoints resume at the last BOUNDARY (the
    # accumulated p.grad is not optimizer state)
    sd = gm.state_dict()
    assert sd["__gm_step__"] == 0
    gm.set_state_dict(sd)
    assert gm._step_i == 0


def test_gradient_merge_static_minimize_refuses():
    import paddle_tpu.nn as nn
    from paddle_tpu import static
    from paddle_tpu.distributed.fleet.gradient_merge import (
        GradientMergeOptimizer)

    paddle.enable_static()
    try:
        main = static.Program()
        with static.program_guard(main):
            x = static.data("x", [4, 2])
            y = static.nn.fc(x, 1)
            loss = y.sum()
            opt = GradientMergeOptimizer(
                paddle.optimizer.SGD(learning_rate=0.1), k_steps=2)
            with pytest.raises(NotImplementedError, match="gradient_merge"):
                opt.minimize(loss)
    finally:
        paddle.disable_static()
