"""Driver-path tests: the exact entry points the driver measures.

Round-2 verdict root-caused both red driver artifacts to these paths
having zero test coverage. (a) runs ``dryrun_multichip(8)`` verbatim in a
subprocess with the forced-CPU env the driver should converge to; (b) pins
pipeline-vs-dense loss parity so the shard_map GPipe schedule can't drift
from the dense path silently.

Reference test pattern: test_dist_base.py:899 (spawn real worker
subprocesses, compare losses against the single-process run).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _tiny_cfg(**kw):
    from paddle_tpu.models.llama import LlamaConfig
    base = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
                num_hidden_layers=4, num_attention_heads=4,
                num_key_value_heads=4, max_position_embeddings=64,
                dtype=jnp.float32, use_remat=False)
    base.update(kw)
    return LlamaConfig(**base)


def test_dryrun_multichip_subprocess():
    """The driver's multichip artifact, verbatim, under the forced-CPU env."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert "OK" in proc.stdout


def test_dryrun_reexec_fallback():
    """When jax initialized without the flag, dryrun re-execs and still
    passes instead of touching the default backend."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""  # flag absent at init time
    code = (
        "import os, jax; jax.devices();"  # init backends before entry import
        "import __graft_entry__ as g; g.dryrun_multichip(8)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert "OK" in proc.stdout


def test_entry_jits():
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[-1] == 256
    assert bool(jnp.isfinite(out).all())


def test_pipeline_loss_matches_dense():
    from jax.sharding import Mesh
    from paddle_tpu.models.llama import init_params, loss_fn
    from paddle_tpu.distributed.pipeline import pipeline_loss_fn

    cfg = _tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S = 4, 16
    batch = {
        "input_ids": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
        "labels": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
    }
    dense_total, dense_ce = loss_fn(cfg, params, batch)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
    pp_total, pp_ce = jax.jit(
        lambda p, b: pipeline_loss_fn(cfg, mesh, 2, p, b))(params, batch)
    np.testing.assert_allclose(np.asarray(pp_ce), np.asarray(dense_ce),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(pp_total), np.asarray(dense_total),
                               rtol=2e-4, atol=2e-5)


def test_pipeline_grads_match_dense():
    from jax.sharding import Mesh
    from paddle_tpu.models.llama import init_params, loss_fn
    from paddle_tpu.distributed.pipeline import pipeline_loss_fn

    cfg = _tiny_cfg(num_hidden_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    batch = {
        "input_ids": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (4, 8)), jnp.int32),
        "labels": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (4, 8)), jnp.int32),
    }
    g_dense = jax.grad(lambda p: loss_fn(cfg, p, batch)[0])(params)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
    g_pp = jax.jit(jax.grad(
        lambda p: pipeline_loss_fn(cfg, mesh, 2, p, batch)[0]))(params)
    for name in ("embed", "lm_head", "norm_f"):
        np.testing.assert_allclose(
            np.asarray(g_pp[name]), np.asarray(g_dense[name]),
            rtol=5e-4, atol=1e-5, err_msg=name)
    # layer-stack grads: compare a couple of leaves
    np.testing.assert_allclose(
        np.asarray(g_pp["layers"]["wq"]), np.asarray(g_dense["layers"]["wq"]),
        rtol=5e-4, atol=1e-5)


def test_zero_optstate_sharding_matches_param_by_path():
    """Adam moments get their own param's placement (path-matched), not a
    same-shape sibling's: wq (column-parallel) and wo (row-parallel) share
    a shape, so shape-keyed matching would collide."""
    from paddle_tpu.distributed.mesh import HybridTopology
    from paddle_tpu.models.llama import build_train_step

    topo = HybridTopology(dp=2, pp=2, sharding=1, mp=2,
                          devices=jax.devices()[:8])
    cfg = _tiny_cfg(num_hidden_layers=4, hidden_size=64,
                    intermediate_size=64, vocab_size=128)
    _, init_fn = build_train_step(cfg, topo, use_pp=False)
    params, opt_state = init_fn(jax.random.PRNGKey(0))

    mu_specs = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(opt_state)[0]:
        key = jax.tree_util.keystr(path)
        if ".mu" in key and hasattr(leaf, "sharding"):
            mu_specs[key] = tuple(leaf.sharding.spec)
    wq = next(s for k, s in mu_specs.items() if "'wq'" in k)
    wo = next(s for k, s in mu_specs.items() if "'wo'" in k)
    # wq: P("pp", None, "mp") + ZeRO dp on dim 1; wo: P("pp", "mp", None)
    # + ZeRO dp on dim 2 — distinct placements for identical shapes
    assert wq == ("pp", "dp", "mp"), wq
    assert wo == ("pp", "mp", "dp"), wo
