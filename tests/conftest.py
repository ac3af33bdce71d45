"""Test config: force an 8-device virtual CPU mesh before jax initializes.

Mirrors the reference's hardware-free CI strategy (SURVEY.md §4: fake
devices / Gloo-CPU fallback): all distributed tests run on
xla_force_host_platform_device_count=8.
"""
import os

# tests (and every child process they spawn) are CPU-only, also on a
# machine whose environment names another platform
os.environ["JAX_PLATFORMS"] = "cpu"
import sys as _sys
_sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import _xla_cpu_flags  # noqa: E402 — stdlib-only, pre-jax

_xla_cpu_flags.ensure(device_count=8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402
import jax  # noqa: E402

# Matmuls default to MXU-style bf16 accumulate; numeric checks need full f32.
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(1234)
    np.random.seed(1234)
    yield


# smoke/slow tiers: `pytest -m "not slow" tests/` is the fast signal
# while iterating; the full suite is the merge gate. Modules listed here
# spend most of their time in XLA compiles of multi-device meshes or
# whole model zoos.
_SLOW_MODULES = {
    "test_graft_entry", "test_pipeline_1f1b", "test_distributed_checkpoint",
    "test_e2e_training", "test_vision_models", "test_auto_parallel",
    "test_jit_inference", "test_launch",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module and item.module.__name__ in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
