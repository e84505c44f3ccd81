#!/usr/bin/env python
"""Causal-LM pretraining: GPT decoder on the synthetic Markov-chain corpus.

The decoder family composes with every parallel mode; this example shows the
two most useful single-knob renderings — plain DP with the Pallas causal
flash kernel, and long-context ring-attention sequence parallelism (pass
``--seq-parallel 4``).  No reference counterpart (SURVEY.md §2.2: no
language models anywhere).

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/train_gpt_lm.py [--seq-parallel 4]
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo root

import jax

from distributed_tensorflow_tpu.data.loaders import load_lm_dataset
from distributed_tensorflow_tpu.engines import (
    SeqParallelEngine, SyncEngine, Trainer)
from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.parallel import mesh as meshlib
from distributed_tensorflow_tpu.utils.harness import resolve_compile_cache


def main(seq_parallel: int = 1) -> None:
    train = load_lm_dataset(seq_len=64, vocab_size=128)
    test = load_lm_dataset(seq_len=64, vocab_size=128, split="test")

    total = jax.device_count()
    if seq_parallel > 1:
        dp = total // seq_parallel
        mesh = meshlib.create_mesh(total, shape=(dp, seq_parallel),
                                   axis_names=("data", "seq"))
        model = create_model("gpt", num_classes=train.num_classes,
                             hidden=64, layers=2, heads=4, ffn=128,
                             max_len=64, attention_impl="ring_flash")
        engine = SeqParallelEngine(model, mesh=mesh, learning_rate=3e-3)
    else:
        dp = total
        mesh = meshlib.create_mesh(total)
        # the Pallas causal kernel on TPU; dense on CPU (interpret-mode
        # Pallas is orders of magnitude slower than XLA there — right for
        # correctness tests, wrong for a demo)
        impl = "flash" if jax.default_backend() == "tpu" else "dense"
        print(f"attention: {impl} (backend {jax.default_backend()})")
        model = create_model("gpt", num_classes=train.num_classes,
                             hidden=64, layers=2, heads=4, ffn=128,
                             max_len=64, attention_impl=impl)
        engine = SyncEngine(model, mesh=mesh, learning_rate=3e-3)

    trainer = Trainer(None, engine=engine)
    fit = trainer.fit(train, epochs=1, batch_size=8 * dp, log_every=20)
    ev = trainer.evaluate(test, batch_size=64)
    print(f"steps={fit['steps']}  elapsed={fit['elapsed']:.1f}s  "
          f"token-accuracy={ev['accuracy']:.3f}  perplexity-proxy "
          f"loss={ev['loss']:.3f}")

    # sample a continuation with the KV cache (greedy): the trained chain
    # model should keep producing plausible transitions
    from distributed_tensorflow_tpu.models.gpt import generate

    params = jax.device_get(engine.eval_params(trainer.state))
    cont = generate(model, params, test.x[:2, :16], max_new_tokens=16,
                    greedy=True)
    print("prompt :", test.x[0, :16].tolist())
    print("sampled:", cont[0].tolist())


if __name__ == "__main__":
    resolve_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--seq-parallel", type=int, default=1)
    main(p.parse_args().seq_parallel)
