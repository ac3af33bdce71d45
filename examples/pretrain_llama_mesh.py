"""Hybrid-parallel pretraining example: tiny llama on an 8-device mesh
(dp=2 x pp=2 x mp=2 — runs on 8 virtual CPU devices; the same script
shape scales to a real pod by changing the topology).

Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     JAX_PLATFORMS=cpu python examples/pretrain_llama_mesh.py
"""
import os

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import _xla_cpu_flags  # noqa: E402 — repo-root helper, pre-jax

_xla_cpu_flags.ensure(device_count=8)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.distributed.mesh import HybridTopology  # noqa: E402
from paddle_tpu.models import llama  # noqa: E402


def main():
    topo = HybridTopology(dp=2, pp=2, mp=2,
                          devices=jax.devices("cpu")[:8])
    cfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=4, max_position_embeddings=64,
        dtype=jnp.float32, use_remat=False)
    step, init_fn = llama.build_train_step(cfg, topo, schedule="1f1b",
                                           n_microbatches=2)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": jnp.asarray(rng.integers(0, 256, (4, 32)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, 256, (4, 32)), jnp.int32),
    }
    with topo.mesh:
        for i in range(3):
            params, opt_state, metrics = step(params, opt_state, batch)
            print(f"step {i}: loss {float(metrics['loss']):.4f}")
    assert np.isfinite(float(metrics["loss"]))


if __name__ == "__main__":
    main()
    # Success: exit without running C++ static destructors. PJRT/TSL
    # thread pools can abort at interpreter shutdown ("Expected N threads to join");
    # a demo script should not fail after training succeeded.
    import sys
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)
