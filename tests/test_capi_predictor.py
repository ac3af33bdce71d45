"""Native C-API serving host, end to end: save a model with jit.save,
compile a pure-C host program against csrc/paddle_tpu_capi.h, run it in a
subprocess, and check its output against the in-process predictor.

Reference analog: paddle/fluid/inference/capi_exp/ C API tests — the
contract that a non-Python process can link the serving library and run
the saved artifact.
"""
import os
import subprocess

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.jit import InputSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CSRC = os.path.join(REPO, "csrc")
CAPI_SO = os.path.join(CSRC, "libpaddle_tpu_capi.so")

HOST_C = r"""
#include <stdio.h>
#include <stdlib.h>
#include "paddle_tpu_capi.h"

int main(int argc, char** argv) {
  if (argc < 3) return 2;
  PD_Predictor* p = PD_PredictorCreate(argv[1]);
  if (!p) { fprintf(stderr, "create: %s\n", PD_GetLastError()); return 3; }

  /* read a flat float32 [1,8] input from the file given in argv[2] */
  float buf[8];
  FILE* f = fopen(argv[2], "rb");
  if (!f || fread(buf, sizeof(float), 8, f) != 8) return 4;
  fclose(f);

  PD_TensorData in;
  in.dtype = PD_DTYPE_FLOAT32;
  in.ndim = 2;
  in.shape[0] = 1; in.shape[1] = 8;
  in.data = buf;

  /* optional 3rd arg "badshape": exercise the error path — a negative
     dim must produce an error return, not a crash */
  if (argc > 3) {
    in.shape[0] = -1;
    PD_TensorData* outs; int n_outs;
    if (PD_PredictorRun(p, &in, 1, &outs, &n_outs) == 0) return 8;
    fprintf(stderr, "badshape: %s\n", PD_GetLastError());
    return 0;
  }

  PD_TensorData* outs; int n_outs;
  if (PD_PredictorRun(p, &in, 1, &outs, &n_outs) != 0) {
    fprintf(stderr, "run: %s\n", PD_GetLastError()); return 5;
  }
  if (n_outs < 1 || outs[0].dtype != PD_DTYPE_FLOAT32) return 6;
  long long n = 1;
  for (int d = 0; d < outs[0].ndim; ++d) n *= outs[0].shape[d];
  const float* data = (const float*)outs[0].data;
  for (long long i = 0; i < n; ++i) printf("%.8e\n", (double)data[i]);

  /* second run through the same predictor must also succeed */
  PD_TensorData* outs2; int n2;
  if (PD_PredictorRun(p, &in, 1, &outs2, &n2) != 0) return 7;
  PD_OutputsDestroy(outs2, n2);

  PD_OutputsDestroy(outs, n_outs);
  PD_PredictorDestroy(p);
  return 0;
}
"""


@pytest.fixture(scope="module")
def c_host(tmp_path_factory):
    """Builds libpaddle_tpu_capi.so (if missing) and the C host binary
    once for the module; returns (host_bin_path, env)."""
    if not os.path.exists(CAPI_SO):
        subprocess.run(["make", "-C", CSRC, "capi"], check=True)
    d = tmp_path_factory.mktemp("capi_host")
    host_src = d / "host.c"
    host_src.write_text(HOST_C)
    host_bin = str(d / "host")
    subprocess.run(
        ["gcc", str(host_src), "-o", host_bin, f"-I{CSRC}",
         f"-L{CSRC}", "-lpaddle_tpu_capi", f"-Wl,-rpath,{CSRC}"],
        check=True)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the embedded interpreter must run on CPU whatever the machine has
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_TPU_CAPI_PLATFORM"] = "cpu"
    return host_bin, env


@pytest.mark.slow
def test_c_host_serves_saved_model(c_host, tmp_path):
    host_bin, env = c_host
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    prefix = str(tmp_path / "model")
    paddle.jit.save(net, prefix, input_spec=[InputSpec([-1, 8], "float32")])

    x = np.random.default_rng(3).standard_normal((1, 8)).astype(np.float32)
    ref = net(paddle.to_tensor(x)).numpy().reshape(-1)

    x_file = tmp_path / "input.bin"
    x_file.write_bytes(x.tobytes())

    proc = subprocess.run([host_bin, prefix, str(x_file)],
                          capture_output=True, text=True, env=env,
                          timeout=240)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    got = np.array([float(line) for line in proc.stdout.split()],
                   dtype=np.float32)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_c_host_rejects_bad_shape(c_host, tmp_path):
    """A negative input dim errors cleanly (no size_t wraparound crash)."""
    host_bin, env = c_host
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    prefix = str(tmp_path / "model")
    paddle.jit.save(net, prefix, input_spec=[InputSpec([-1, 8], "float32")])
    x_file = tmp_path / "input.bin"
    x_file.write_bytes(b"\0" * 32)
    proc = subprocess.run([host_bin, prefix, str(x_file), "badshape"],
                          capture_output=True, text=True, env=env,
                          timeout=240)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "badshape:" in proc.stderr and "shape" in proc.stderr


@pytest.mark.slow
def test_c_host_reports_errors(c_host, tmp_path):
    """A bad model prefix must fail with a message, not crash the host."""
    host_bin, env = c_host
    dummy = tmp_path / "input.bin"
    dummy.write_bytes(b"\0" * 32)
    proc = subprocess.run([host_bin, str(tmp_path / "nonexistent"),
                           str(dummy)],
                          capture_output=True, text=True, env=env,
                          timeout=240)
    assert proc.returncode == 3
    assert "create:" in proc.stderr
