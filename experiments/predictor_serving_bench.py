"""Measure the serving path (the AnalysisPredictor analog): ResNet-50
eval through inference.Config/create_predictor — fp32 vs bf16 vs
int8-compute, batch 1 and 32.

WALL-CLOCK numbers count the host's dispatch and the input upload with
the predictor. The r5 `--device-time` mode leaves them out with
paddle_tpu.inference.device_time_per_run (scan-slope extraction: the
predict program runs N times inside one dispatch as a dependent chain;
the slope over two N cancels the fixed dispatch cost exactly) — those
are per-inference device times and feed the BASELINE serving row.

Usage: python experiments/predictor_serving_bench.py [--device-time]
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.inference import Config, PrecisionType, create_predictor

ITERS = 30


def bench(pred, x):
    out = pred.run([x])
    np.asarray(out[0]).sum()
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = pred.run([x])
    np.asarray(out[0]).sum()
    return (time.perf_counter() - t0) / ITERS


def main():
    device_time = "--device-time" in sys.argv
    from paddle_tpu.models.resnet import resnet50
    paddle.seed(0)
    model = resnet50(num_classes=1000, data_format="NHWC")
    model.eval()
    rng = np.random.RandomState(0)

    for batch in (1, 32):
        x = rng.randn(batch, 3, 224, 224).astype(np.float32)
        xt = paddle.to_tensor(x)
        results = []
        for tag, setup in (
            ("fp32", lambda c: None),
            ("bf16", lambda c: c.enable_tpu(
                precision=PrecisionType.Bfloat16)),
            ("bf16+int8", lambda c: (c.enable_tpu(
                precision=PrecisionType.Bfloat16),
                c.enable_int8_compute())),
        ):
            cfg = Config().from_layer(model, input_spec=[xt])
            setup(cfg)
            try:
                pred = create_predictor(cfg)
                if device_time:
                    from paddle_tpu.inference import device_time_per_run
                    dt = device_time_per_run(pred, [x])
                else:
                    dt = bench(pred, x)
                results.append(
                    f"{tag} {dt * 1e3:6.2f} ms ({batch / dt:7.1f} img/s)")
            except Exception as e:  # noqa: BLE001
                results.append(f"{tag} FAILED {type(e).__name__}: "
                               f"{str(e)[:60]}")
        print(f"b{batch}: " + " | ".join(results))


if __name__ == "__main__":
    main()
