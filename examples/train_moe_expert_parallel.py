#!/usr/bin/env python
"""Mixture-of-Experts training with expert parallelism.

Experts shard over an 'expert' mesh axis (GShard/Switch dense-dispatch,
models/moe.py); XLA lowers the dispatch einsums to all-to-alls over ICI.
No reference counterpart (SURVEY.md §2.2: no MoE anywhere).

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/train_moe_expert_parallel.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo root

import jax

from distributed_tensorflow_tpu.data.loaders import load_dataset
from distributed_tensorflow_tpu.engines import ExpertParallelEngine
from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.parallel import mesh as meshlib
from distributed_tensorflow_tpu.utils.harness import resolve_compile_cache


def main(expert_parallel: int = 4, num_experts: int = 8) -> None:
    total = jax.device_count()
    dp = total // expert_parallel
    mesh = meshlib.create_mesh(
        total, shape=(dp, expert_parallel),
        axis_names=(meshlib.DATA_AXIS, meshlib.EXPERT_AXIS))
    print(f"mesh: data={dp} x expert={expert_parallel}, "
          f"{num_experts} experts ({num_experts // expert_parallel}/device)")

    train = load_dataset("mnist", split="train")
    test = load_dataset("mnist", split="test")
    model = create_model("moe", num_classes=train.num_classes,
                         num_experts=num_experts, partition_experts=True)

    eng = ExpertParallelEngine(model, mesh=mesh, learning_rate=1e-3)
    state = eng.init_state(jax.random.key(0), train.x[:total])
    for step, (bx, by, _) in enumerate(
            train.batches(16 * total, shuffle=True, drop_remainder=True)):
        state, m = eng.step(state, *eng.shard_batch(bx, by))
        if step % 20 == 0:
            print(f"step {step}  task-loss {float(m['loss']):.4f}  "
                  f"total {float(m['total_loss']):.4f}")
    ev = eng.evaluate(state, test)
    print(f"accuracy={ev['accuracy']:.4f}")


if __name__ == "__main__":
    resolve_compile_cache()
    main()
