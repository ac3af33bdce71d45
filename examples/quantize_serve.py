"""PTQ -> int8 artifact -> serve: the quantized-deployment workflow.

Reference analog: the static post-training-quantization demo flow
(QuantizationTransformPass calibrate -> QuantizationFreezePass ->
C++ predictor). Here: observe -> calibrate -> convert(to_int8=True) ->
jit.save -> inference.Predictor; the same artifact also serves from
pure C via libpaddle_tpu_capi.so (see examples/serve_capi.c).

Run: python examples/quantize_serve.py   (CPU-safe; ~30 s)
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import inference
from paddle_tpu.jit import InputSpec
from paddle_tpu.quantization import (KLObserver, PTQ, QuantConfig,
                                     AbsmaxObserver, QuanterFactory,
                                     QuantizedLinear)


def main():
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                        nn.Linear(128, 32), nn.ReLU(),
                        nn.Linear(32, 10))
    net.eval()
    rng = np.random.default_rng(0)
    calib = rng.standard_normal((8, 32, 64)).astype(np.float32)
    x_eval = rng.standard_normal((16, 64)).astype(np.float32)
    ref = net(paddle.to_tensor(x_eval)).numpy()

    # 1. observe: KL entropy calibration for activations (robust to
    # outliers), absmax for weights
    cfg = QuantConfig(activation=QuanterFactory(KLObserver),
                      weight=QuanterFactory(AbsmaxObserver))
    ptq = PTQ(cfg)
    observed = ptq.quantize(net)
    for batch in calib:
        observed(paddle.to_tensor(batch))

    # 2. freeze to int8 compute
    q = ptq.convert(observed, to_int8=True)
    q.eval()
    n_int8 = sum(isinstance(s, QuantizedLinear) for s in q.sublayers())
    out = q(paddle.to_tensor(x_eval)).numpy()
    rel = float(np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9))
    print(f"{n_int8} layers frozen to int8 compute; "
          f"eager rel err vs fp32: {rel:.4f}")

    # 3. export + serve
    d = tempfile.mkdtemp()
    prefix = os.path.join(d, "mlp_int8")
    paddle.jit.save(q, prefix,
                    input_spec=[InputSpec([16, 64], "float32")])
    fp32_prefix = os.path.join(d, "mlp_fp32")
    paddle.jit.save(net, fp32_prefix,
                    input_spec=[InputSpec([16, 64], "float32")])
    shrink = (os.path.getsize(prefix + ".pdiparams")
              / os.path.getsize(fp32_prefix + ".pdiparams"))
    pred = inference.create_predictor(
        inference.Config(prefix + ".pdmodel"))
    got = pred.run([x_eval])[0]
    rel_served = float(np.abs(got - ref).max()
                       / (np.abs(ref).max() + 1e-9))
    print(f"served rel err: {rel_served:.4f}; "
          f"weights payload: {shrink:.2f}x of fp32")
    assert rel_served < 0.1 and shrink < 0.5
    print("int8 serving flow OK")


if __name__ == "__main__":
    main()
