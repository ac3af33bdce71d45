"""paddle_tpu — a TPU-native deep-learning framework.

Capability target: PaddlePaddle (reference at /root/reference, see
/root/repo/SURVEY.md). Architecture: jax/XLA for the compute path (every op
is a jnp/lax lowering, fused by XLA), Pallas for hot fused kernels, a single
jax.sharding.Mesh for all 4-D+ hybrid parallelism, and a stateful
Tensor/Layer facade giving paddle's eager UX on top of jax's functional core.

Top-level namespace mirrors `import paddle`.
"""
from __future__ import annotations

__version__ = "0.1.0"

from .core import (Tensor, to_tensor, no_grad, enable_grad, is_grad_enabled,
                   set_grad_enabled, CPUPlace, TPUPlace, CustomPlace,
                   set_flags, get_flags)
from .core.place import (set_device, get_device, device_count,
                         is_compiled_with_cuda, is_compiled_with_tpu)
from .core.dtype import (bool_ as bool8, uint8, int8, int16, int32, int64,
                         float16, bfloat16, float32, float64, complex64,
                         complex128, set_default_dtype, get_default_dtype,
                         finfo, iinfo)
from .framework.random import seed, get_rng_state, set_rng_state
from .framework.param_attr import ParamAttr
from .compat import (dtype, batch, tolist, check_shape, CUDAPlace,
                     CUDAPinnedPlace, NPUPlace, get_cuda_rng_state,
                     set_cuda_rng_state)
from .core.dtype import bool_ as bool  # noqa: A001 — reference exports
# paddle.bool as a dtype name (shadows the builtin inside this
# namespace only, exactly as the reference does)

from .tensor import *  # noqa: F401,F403 — the ~200-op tensor surface
from .tensor import logic as _logic

grad_enabled = is_grad_enabled
is_tensor = _logic.is_tensor

from . import tensor  # noqa: E402
from . import autograd  # noqa: E402
from . import nn  # noqa: E402
from . import optimizer  # noqa: E402
from . import amp  # noqa: E402
from . import io  # noqa: E402
from . import jit  # noqa: E402
from . import distributed  # noqa: E402
from . import vision  # noqa: E402
from . import metric  # noqa: E402
from . import framework  # noqa: E402
# `from .tensor import *` above re-exported the tensor.linalg submodule
# under the name `linalg`, which `from . import linalg` would silently
# reuse — import the real namespace module explicitly instead
import importlib as _importlib  # noqa: E402
linalg = _importlib.import_module(".linalg", __name__)
from . import fft  # noqa: E402
from . import signal  # noqa: E402
from . import sparse  # noqa: E402
from . import profiler  # noqa: E402
from . import runtime  # noqa: E402
from . import analysis  # noqa: E402
from . import incubate  # noqa: E402
from . import inference  # noqa: E402
from . import hapi  # noqa: E402
from . import device  # noqa: E402
from . import static  # noqa: E402
from .static.program import (enable_static, disable_static)  # noqa: E402
from . import version  # noqa: E402

__version__ = version.full_version


def flops(net, input_size, custom_ops=None, print_detail=False):
    from .utils import flops as _flops
    return _flops(net, input_size, custom_ops=custom_ops,
                  print_detail=print_detail)


def disable_signal_handler():
    """Parity no-op: the reference unhooks its C++ SIGSEGV/SIGBUS dump
    handlers (paddle/fluid/platform/init.cc); this build installs none."""


def get_cudnn_version():
    return None  # no CUDA in the build (reference returns e.g. 8200)


class LazyGuard:
    """Reference: paddle.LazyGuard defers parameter materialization so
    giant models can be sharded before init. TPU-native equivalent: use
    the functional init path jitted with output shardings
    (models/llama.py build_train_step init_fn) — arrays are then created
    directly on their owning devices. This guard exists for source
    compatibility; eager Layers under it initialize normally."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def in_dynamic_mode():
    from .static.program import in_static_mode
    return not in_static_mode()


in_dygraph_mode = in_dynamic_mode
from . import distribution  # noqa: E402
from . import geometric  # noqa: E402
from . import onnx  # noqa: E402
from . import utils  # noqa: E402
from . import quantization  # noqa: E402
from . import text  # noqa: E402
from . import audio  # noqa: E402

from .framework.io import save, load  # noqa: E402
from .autograd.functional import grad  # noqa: E402
from .hapi.model import Model, summary  # noqa: E402
from .vision import models  # noqa: E402

DataParallel = distributed.DataParallel
