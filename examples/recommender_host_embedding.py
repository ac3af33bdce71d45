"""Recommender-style training with an out-of-accelerator-memory embedding.

The parameter-server regime on the TPU stack (reference:
paddle/fluid/distributed/ps + heter-PS pull/push workers): a 1M x 64
embedding table (~256 MB) lives in host RAM across 4 shards; each step
pulls only the rows the batch touches onto the device, the dense tower
trains on-device under jit, and the backward sparse-pushes row
gradients into the host-side Adagrad.

Run: python examples/recommender_host_embedding.py   (CPU or TPU)
"""
import os

# CPU demo by default (the host-RAM pulls dominate; swap the platform
# pin to run the dense tower on a real chip)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.distributed.ps import HostEmbedding  # noqa: E402


def main():
    V, D, B, SLOTS = 1_000_000, 64, 256, 8
    emb = HostEmbedding(V, D, n_shards=4, optimizer="adagrad", lr=0.05,
                        seed=0, device_budget_bytes=64 << 20)
    print(f"embedding: {emb.table_nbytes / 1e6:.0f} MB in host RAM "
          f"({emb.n_shards} shards); device sees {B * SLOTS}x{D} per step")

    rng = np.random.default_rng(0)
    w_true = rng.standard_normal((D, 1)).astype(np.float32) * 0.1

    params = {"w1": jnp.asarray(rng.standard_normal((D, 32)) * 0.1,
                                jnp.float32),
              "w2": jnp.asarray(rng.standard_normal((32, 1)) * 0.1,
                                jnp.float32),
              "token": emb.init_token()}

    def loss_fn(params, ids, y):
        rows = emb(ids, params["token"])          # [B, SLOTS, D] pull
        pooled = jnp.mean(rows, axis=1)           # mean-pool the slots
        h = jnp.tanh(pooled @ params["w1"])
        pred = h @ params["w2"]
        return jnp.mean((pred - y) ** 2)

    @jax.jit
    def step(params, ids, y):
        loss, g = jax.value_and_grad(loss_fn)(params, ids, y)
        params = jax.tree_util.tree_map(lambda p, gg: p - 0.1 * gg,
                                        params, g)
        return params, loss

    # fixed synthetic CTR-ish labels from the UNTRAINED table (pulled
    # before any gradient push mutates it)
    batches = []
    for _ in range(30):
        ids = rng.integers(0, V, (B, SLOTS))
        y = (np.mean(emb.pull_sparse(ids), axis=1) @ w_true
             ).astype(np.float32) + 1.0
        batches.append((ids, y))

    losses = []
    for it, (ids, y) in enumerate(batches):
        params, loss = step(params, jnp.asarray(ids), jnp.asarray(y))
        losses.append(float(loss))
        if it % 10 == 0:
            print(f"step {it}: loss {losses[-1]:.4f}")
    print(f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert losses[-1] < losses[0]


if __name__ == "__main__":
    main()
    # Success: skip C++ static destructors — PJRT/TSL thread pools can
    # abort at interpreter shutdown after training already succeeded.
    import sys
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)
