"""chip_smoke.py rehearsed off-chip: its trainer and server functions run
at llama-debug width on CPU (Pallas in interpret mode, no kernel expected
in the lowered text), and the script itself refuses to pass without a TPU
— it has no flag that lets it."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models import llama
from paddle_tpu.ops import pallas_ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setattr(pallas_ops, "_INTERPRET", True)


def test_trainer_function_at_debug_width():
    from paddle_tpu.distributed.plan import Plan
    out = chip_smoke.run_trainer(
        llama.preset("llama-debug"), batch=2, seq=32, steps=3, plan=Plan(),
        expect_kernels=set())
    assert len(out["ce"]) == 3 and out["ce"][-1] < out["ce"][0]
    with pytest.raises(AssertionError, match="runs Pallas kernels"):
        chip_smoke.run_trainer(
            llama.preset("llama-debug"), batch=2, seq=32, steps=1,
            plan=Plan(), expect_kernels=chip_smoke.TRAIN_KERNELS)


def test_trainer_refuses_a_flash_forward_run_twice(monkeypatch):
    """Layers that keep q, k and v but not the flash forward's output run
    that kernel again in the backward pass: the check reads it from the
    lowered text's scopes."""
    from paddle_tpu.distributed.plan import Plan
    cfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=256, intermediate_size=512,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=256, dtype=jnp.float32)
    kw = dict(batch=2, seq=256, steps=2, plan=Plan(), expect_kernels=set())
    chip_smoke.run_trainer(cfg, **kw)
    monkeypatch.setattr(llama, "layer_names",
                        lambda *a, **k: llama.SAVED_NAMES[1:])
    with pytest.raises(AssertionError,
                       match=r"_flash_fwd_kernel_resident'\] a second time"):
        chip_smoke.run_trainer(cfg, **kw)


def test_server_functions_at_debug_width():
    # room for the prompt that spans three default (128-token) pages
    cfg = llama.preset("llama-debug", max_position_embeddings=512)
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    chip_smoke.run_serving_passes(
        cfg, params, n_requests=4, n_new=4,
        dense_kernels=set(), int8_kernels=set())


@pytest.mark.parametrize("interpreted", [False, True])
def test_scan_parity_at_debug_width(interpreted, monkeypatch):
    """Off the chip both sides are the XLA body, or (interpreted) the kernel
    against it: an interpreted kernel is no Mosaic call in the lowered text."""
    monkeypatch.setattr(pallas_ops, "_INTERPRET", interpreted)
    chip_smoke.run_scan_parity(rows=(16, 8), inner=128, state=16, chunk=4,
                               expect_kernel=False)
    with pytest.raises(AssertionError, match="runs Pallas kernels"):
        chip_smoke.run_scan_parity(rows=(8,), inner=128, state=16, chunk=4,
                                   expect_kernel=True)


@pytest.mark.parametrize("interpreted", [False, True])
def test_latent_and_experts_parity_at_debug_width(interpreted, monkeypatch):
    """The two phases of the DeepSeek-V2 kernels, as the scan's above."""
    monkeypatch.setattr(pallas_ops, "_INTERPRET", interpreted)
    # chunks of 8 tokens x 4 heads: rows of one and of three tokens take
    # the walk's 16-row turn, whole chunks every row
    chip_smoke.run_latent_parity(rows=6, heads=4, lanes=256, v_lanes=128,
                                 blocks=3, chunk=8, dtype=jnp.float32,
                                 expect_kernel=False)
    chip_smoke.run_experts_parity(experts=5, hidden=128, width=128,
                                  per_token=2, tokens=(70, 3),
                                  dtype=jnp.float32, expect_kernel=False)
    with pytest.raises(AssertionError, match="run Pallas kernels"):
        chip_smoke.run_latent_parity(rows=2, heads=4, lanes=256, v_lanes=128,
                                     blocks=2, chunk=4, dtype=jnp.float32,
                                     expect_kernel=True)


def test_server_check_reads_what_the_engine_served():
    cfg = llama.preset("llama-debug", max_position_embeddings=512)
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    eng, served = chip_smoke.run_server(
        cfg, params, kv_dtype=None, n_requests=2, n_new=4,
        expect_kernels=set())
    tol = dict(logits_tol=chip_smoke.DENSE_LOGITS_REL_TOL,
               token_gap=chip_smoke.DENSE_TOKEN_GAP)
    chip_smoke.check_served(eng, params, served, **tol)
    # tokens other than the ones the reference would choose
    wrong = [(p, [(t + 1) % cfg.vocab_size for t in out])
             for p, out in served]
    with pytest.raises(AssertionError, match="trails the reference"):
        chip_smoke.check_served(eng, params, wrong, **tol)
    # an engine holding other weights than the caller handed it
    eng.params = llama.init_params(cfg, jax.random.PRNGKey(2))
    with pytest.raises(AssertionError, match="served logits off"):
        chip_smoke.check_served(eng, params, served, **tol)
    eng.shutdown()


def test_script_exits_nonzero_and_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert '"platform": "cpu"' in proc.stdout   # names the device it found
