"""ResNet-50 train-step throughput on the TPU chip (VERDICT r2 next #8 —
the first non-llama hardware number; BASELINE.json configs[0]).

Runs the reference ResNet-50 (vision/models/resnet.py) through the general
auto-parallel Engine (distributed/engine.py) — the conv path on the MXU +
BN buffer capture + donated AdamW — with the chained steady-state
measurement of benchmarks/_timing.py (sync once per chain via device_get).

    python benchmarks/resnet_bench.py [B] [IMG] [chain] [samples]

Prints one JSON line: images/sec + step ms.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def main():
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    img = int(sys.argv[2]) if len(sys.argv) > 2 else 224
    chain = int(sys.argv[3]) if len(sys.argv) > 3 else 5
    samples = int(sys.argv[4]) if len(sys.argv) > 4 else 5

    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.engine import Engine, Strategy
    from paddle_tpu.nn import functional as F
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.vision.models import resnet50

    model = resnet50(num_classes=1000)
    eng = Engine(model, loss=lambda logits, y: F.cross_entropy(logits, y),
                 optimizer=AdamW(learning_rate=1e-3,
                                 moment_dtype=jnp.bfloat16),
                 strategy=Strategy(amp=True))  # bf16 convs on the MXU

    rng = np.random.RandomState(0)
    # device-resident batch: a [64,3,224,224] f32 host batch is ~38 MB per
    # step of host-to-device copy — input-pipeline cost, not train-step
    # throughput, so stage the fixed batch onto the chip once
    x = jnp.asarray(rng.rand(B, 3, img, img).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 1000, (B, 1)).astype(np.int32))
    jax.block_until_ready(x)

    t0 = time.time()
    loss = eng.step(x, y)
    float(jax.device_get(loss._value if hasattr(loss, "_value") else loss))
    compile_s = time.time() - t0

    import os as _os
    import sys as _sys
    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))))
    from benchmarks._timing import timed_chain
    times = timed_chain(lambda: eng.step(x, y), chain, samples)
    loss = eng.step(x, y)
    dt = float(np.median(times))
    print(json.dumps({
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(B / dt, 1),
        "unit": "images/s",
        "config": {"batch": B, "image": img, "chain": chain,
                   "samples": samples, "optimizer": "AdamW bf16-moments"},
        "step_ms_median": round(dt * 1e3, 2),
        "step_ms_min": round(min(times) * 1e3, 2),
        "compile_s": round(compile_s, 1),
        "device": str(getattr(jax.devices()[0], "device_kind", "?")),
        "loss": float(jax.device_get(
            loss._value if hasattr(loss, "_value") else loss)),
    }))


if __name__ == "__main__":
    main()
