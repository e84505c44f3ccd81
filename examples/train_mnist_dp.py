#!/usr/bin/env python
"""Data-parallel MNIST training via the library API.

The library rendering of the reference's default workload (reference
initializer.py:12-21 MLP + MNIST): sync DP over every local device, full
test-set eval.  Runs on real TPUs or the fake CPU mesh:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/train_mnist_dp.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo root

from distributed_tensorflow_tpu.data.loaders import load_dataset
from distributed_tensorflow_tpu.engines import Trainer
from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.parallel import mesh as meshlib
from distributed_tensorflow_tpu.utils.harness import resolve_compile_cache


def main() -> None:
    mesh = meshlib.create_mesh()
    n = mesh.devices.size
    print(f"mesh: {n} devices on axis '{meshlib.DATA_AXIS}'")

    model = create_model("cnn", num_classes=10)
    train = load_dataset("mnist", split="train")
    test = load_dataset("mnist", split="test")

    trainer = Trainer(model, mesh=mesh, learning_rate=1e-3)
    fit = trainer.fit(train, epochs=1, batch_size=64 * n, log_every=50)
    ev = trainer.evaluate(test)
    print(f"steps={fit['steps']}  {fit['examples_per_sec']:.0f} ex/s  "
          f"accuracy={ev['accuracy']:.4f}")


if __name__ == "__main__":
    resolve_compile_cache()
    main()
