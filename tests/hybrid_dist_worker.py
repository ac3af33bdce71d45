"""Multi-process HYBRID-parallel trainer: multi-host GSPMD shape.

Launched by test_multiprocess_dist.py as 2 processes x 4 virtual CPU
devices = one global 8-device mesh (the 2-hosts-x-4-chips TPU-pod
execution shape; reference workhorse:
test_parallel_dygraph_pipeline_parallel.py + test_dist_base.py:899).

The device list is reordered so the pipeline (or ring-attention) axis
SPANS the process boundary — shard_map ppermute/collective traffic must
cross processes, which is exactly where multi-host bugs live. Each rank
asserts the sharded step's cross-entropy matches a locally computed
single-device reference (same cfg/seed/batch) and reports via
RESULT:. Variants: 1F1B pipeline hops, the ring-attention ring, and the
dedicated ZeRO sharding axis each span the process boundary.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import _xla_cpu_flags  # noqa: E402 — stdlib-only, must precede jax

PER_PROC = int(os.environ.get("PTQ_DEVICES_PER_PROC") or 4)
_xla_cpu_flags.ensure(device_count=PER_PROC)


def _boundary_spanning_devices(nprocs, per_proc):
    """Global device order (dp, proc, inner): the MIDDLE topology axis
    alternates processes, so pp/sp neighbors are cross-process."""
    import numpy as np
    import jax
    devs = np.array(jax.devices())
    assert devs.size == nprocs * per_proc, devs.size
    inner = per_proc // 2
    return list(devs.reshape(nprocs, 2, inner)
                .transpose(1, 0, 2).reshape(-1))


def _run_variant(label, *, dp, pp, sp, mp, schedule, nprocs,
                 per_proc, sharding=1):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed.mesh import HybridTopology
    from paddle_tpu.models import llama

    devices = _boundary_spanning_devices(nprocs, per_proc)
    topo = HybridTopology(dp=dp, pp=pp, sp=sp, mp=mp,
                          sharding=sharding, devices=devices)
    kw = dict(num_hidden_layers=2 * max(pp, 1),
              num_attention_heads=2 * max(mp, sp),
              num_key_value_heads=2 * max(mp, sp),
              hidden_size=16 * mp * max(pp, 1) * max(sp, 1),
              intermediate_size=32 * mp,
              vocab_size=64 * mp)
    # layers rematerialised, the default: the step then sizes what they
    # keep to the memory a device reports, which only a device this
    # process addresses answers (the cp and zero variants go that way)
    cfg = llama.LlamaConfig(
        max_position_embeddings=64, dtype=jnp.float32, **kw)
    n_micro = 2 * pp if pp > 1 else None
    step_fn, init_fn = llama.build_train_step(
        cfg, topo, use_pp=(pp > 1), n_microbatches=n_micro,
        schedule=schedule)
    params, opt_state = init_fn(jax.random.PRNGKey(0))

    B = max(2 * dp * sharding, (n_micro or 1) * dp * sharding)
    S = 16 * max(sp, 1)
    rng = np.random.default_rng(0)
    host_batch = {
        "input_ids": rng.integers(0, cfg.vocab_size, (B, S)).astype(
            np.int32),
        "labels": rng.integers(0, cfg.vocab_size, (B, S)).astype(
            np.int32),
    }
    sh = NamedSharding(topo.mesh, P(topo.batch_axes, None))
    # every process holds the full deterministic batch; each contributes
    # the shards it addresses (works however axes map onto processes)
    batch = {k: jax.make_array_from_callback(
        v.shape, sh, lambda idx, v=v: v[idx])
        for k, v in host_batch.items()}

    params, opt_state, metrics = step_fn(params, opt_state, batch)
    ce = float(jax.device_get(metrics["ce"]))
    loss = float(jax.device_get(metrics["loss"]))
    assert np.isfinite(loss), f"{label}: non-finite loss {loss}"

    # local single-device reference: same deterministic init + batch
    ref_params = jax.jit(lambda k: llama.init_params(cfg, k))(
        jax.random.PRNGKey(0))
    _, ref_ce = jax.jit(lambda p, b: llama.loss_fn(cfg, p, b))(
        ref_params, host_batch)
    ref_ce = float(ref_ce)
    np.testing.assert_allclose(
        ce, ref_ce, rtol=2e-4, atol=2e-4,
        err_msg=f"{label}: cross-process CE {ce} != local ref {ref_ce}")
    return {"label": label, "ce": ce, "ref_ce": ref_ce, "loss": loss}


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    nprocs = int(os.environ["PADDLE_TRAINERS_NUM"])
    per_proc = PER_PROC

    import jax
    jax.config.update("jax_platforms", "cpu")

    from _dist_rendezvous import rendezvous, ordered_exit
    store = rendezvous(rank, nprocs, int(os.environ["PTQ_STORE_PORT"]),
                       int(os.environ["PTQ_COORD_PORT"]))

    import paddle_tpu.distributed as dist
    dist.init_parallel_env()
    assert jax.process_count() == nprocs, jax.process_count()
    n_dev = len(jax.devices())
    assert n_dev == nprocs * per_proc, \
        f"expected {nprocs * per_proc} global devices, got {n_dev}"

    results = []
    # 1. dp2 x pp2 x mp2: 1F1B pipeline whose ppermute hops cross the
    #    process boundary; TP within each process; ZeRO-1 over dp
    results.append(_run_variant("pp-xproc", dp=2, pp=2, sp=1, mp=2,
                                schedule="1f1b", nprocs=nprocs,
                                per_proc=per_proc))
    # 2. dp2 x sp2 x mp2: ring-attention context parallelism with the
    #    ring spanning processes
    results.append(_run_variant("cp-xproc", dp=2, pp=1, sp=2, mp=2,
                                schedule="gpipe", nprocs=nprocs,
                                per_proc=per_proc))
    # 3. dp2 x sharding2 x mp2: the DEDICATED ZeRO axis spans the
    #    process boundary (param/opt-state shards live on different
    #    hosts; the gather/scatter traffic crosses DCN in production)
    results.append(_run_variant("zero-xproc", dp=2, pp=1, sp=1, mp=2,
                                schedule="gpipe", nprocs=nprocs,
                                per_proc=per_proc, sharding=2))

    print("RESULT:" + json.dumps({"rank": rank, "world": nprocs,
                                  "variants": results}), flush=True)
    ordered_exit(store, rank, nprocs)


if __name__ == "__main__":
    main()
