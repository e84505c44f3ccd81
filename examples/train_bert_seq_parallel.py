#!/usr/bin/env python
"""Long-context BERT-tiny fine-tune with ring-attention sequence parallelism.

Shards the sequence dimension over a 'seq' mesh axis: each device holds a
slice of every sequence, and attention runs as a blockwise ppermute ring
(parallel/ring_attention.py) so the full sequence never materializes on one
device.  No reference counterpart (SURVEY.md §2.2: no attention anywhere).

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/train_bert_seq_parallel.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo root

import jax

from distributed_tensorflow_tpu.data.loaders import load_text_dataset
from distributed_tensorflow_tpu.engines import SeqParallelEngine
from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.parallel import mesh as meshlib
from distributed_tensorflow_tpu.utils.harness import resolve_compile_cache


def main(seq_parallel: int = 4) -> None:
    total = jax.device_count()
    dp = total // seq_parallel
    mesh = meshlib.create_mesh(
        total, shape=(dp, seq_parallel),
        axis_names=(meshlib.DATA_AXIS, meshlib.SEQ_AXIS))
    print(f"mesh: data={dp} x seq={seq_parallel}")

    train = load_text_dataset("glue_synth", split="train", seq_len=128)
    test = load_text_dataset("glue_synth", split="test", seq_len=128)
    model = create_model("bert_tiny", num_classes=train.num_classes,
                         attention_impl="ring")

    eng = SeqParallelEngine(model, mesh=mesh, learning_rate=3e-4)
    state = eng.init_state(jax.random.key(0), train.x[:dp])
    for epoch in range(1):
        for step, (bx, by, _) in enumerate(
                train.batches(8 * dp, shuffle=True, epoch=epoch,
                              drop_remainder=True)):
            state, m = eng.step(state, *eng.shard_batch(bx, by))
            if step % 50 == 0:
                print(f"step {step}  loss {float(m['loss']):.4f}")
    ev = eng.evaluate(state, test)
    print(f"accuracy={ev['accuracy']:.4f}")


if __name__ == "__main__":
    resolve_compile_cache()
    main()
