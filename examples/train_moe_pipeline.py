#!/usr/bin/env python
"""MoE pipeline training: dp×pp×ep on one 3-D mesh.

A GPT decoder split over pipeline stages (GPipe collective schedule,
engines/pipeline.py) whose stage blocks carry routed MoE FFNs — the
experts shard over an 'expert' GSPMD auto axis while the pipe ppermute
ring stays manual, so stage activations ride ICI between stages AND
expert dispatch rides ICI within them.  No reference counterpart
(SURVEY.md §2.2: no pipeline, no MoE anywhere).

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/train_moe_pipeline.py

CLI spelling of the same run:
  python initializer.py -m t -pp 2 -ep 2 --model gpt --dataset lm_synth \
      --num-experts 4
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo root

import jax
import numpy as np

from distributed_tensorflow_tpu.data.loaders import load_lm_dataset
from distributed_tensorflow_tpu.engines.pipeline import PipelineEngine
from distributed_tensorflow_tpu.models.gpt import gpt_pipeline_stages
from distributed_tensorflow_tpu.parallel import mesh as meshlib
from distributed_tensorflow_tpu.utils.harness import resolve_compile_cache


def main(pipeline_parallel: int = 2, expert_parallel: int = 2,
         num_experts: int = 4) -> None:
    total = jax.device_count()
    dp = total // (pipeline_parallel * expert_parallel)
    mesh = meshlib.create_mesh(
        total, shape=(dp, pipeline_parallel, expert_parallel),
        axis_names=(meshlib.DATA_AXIS, meshlib.PIPE_AXIS,
                    meshlib.EXPERT_AXIS))
    print(f"mesh: data={dp} x pipe={pipeline_parallel} x "
          f"expert={expert_parallel}; {num_experts} experts "
          f"({num_experts // expert_parallel}/expert-device), "
          f"{pipeline_parallel} stages")

    # small synthetic corpus: the demo is the composition, not the corpus
    train = load_lm_dataset(seq_len=32, vocab_size=256, n_train=512)
    eng = PipelineEngine(
        microbatches=4, mesh=mesh, learning_rate=1e-3,
        stages=gpt_pipeline_stages(
            vocab_size=train.num_classes, hidden=64, heads=4, ffn=128,
            max_len=32, moe_experts=num_experts, partition_experts=True))

    state = eng.init_state(jax.random.key(0), train.x[:dp])
    batch = 8 * dp
    for step, (bx, by, _) in enumerate(
            train.batches(batch, shuffle=True, drop_remainder=True)):
        state, m = eng.step(state, *eng.shard_batch(bx, by))
        if step % 20 == 0:
            print(f"step {step}  loss {float(m['loss']):.4f}  "
                  f"overflow {float(m['overflow']):.3f}")
    ev = eng.evaluate(state, train)
    print(f"final train accuracy={ev['accuracy']:.4f}  "
          f"perplexity={float(np.exp(ev['loss'])):.2f}")


if __name__ == "__main__":
    resolve_compile_cache()
    main()
