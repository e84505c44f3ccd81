#!/usr/bin/env python
"""chip_smoke.py — the trainer and the server, once, on the chip.

One process drives the repository's main path through the entry point a
user calls, ``distributed_tensorflow_tpu.cli.main(argv, dataset_fn=...)``
(what ``initializer.py`` calls), over all local devices, at the widest
configuration the repository names for its own pre-LN decoder: hidden 512,
8 layers, 8 heads (head size 64), ffn 2048, vocabulary 16,384, sequence
1,024, bfloat16, per-chip batch 8.  Weights are random from a seed and the
corpus is generated from a seed; nothing is read from the network.

Phases, each checked; any failure is a non-zero exit:

  1 device   a TPU backend, or nothing runs (a CPU is an error here)
  2 train    16 steps with the Pallas flash kernel; finite, falling loss
  3 serve    8 requests through the slot KV cache
  4 kernels  flash fwd+bwd against the dense reference, compiled by
             Mosaic (asserted on the lowered text)
  5 cache    the compile-cache directory and its entries before and after

The last two lines of standard output are JSON.  The line before the last,
prefixed ``chip_smoke: report``, carries the versions, the configuration,
each phase's verdict and wall time, and the total.  The last line is the
verdict alone, ``{"ok": ..., "device": {"platform", "kind", "count"}}``
with the device as JAX reports it, and nothing else.
"""

from __future__ import annotations

import json
import math
import sys
import tempfile
import time
import traceback
from pathlib import Path

import numpy as np

WIDTH = {"hidden": 512, "layers": 8, "heads": 8, "ffn": 2048,
         "max_len": 1024}
VOCAB = 16384
SEQ = 1024
PER_CHIP_BATCH = 8
TRAIN_STEPS = 16          # two steps_per_call=8 chunks
TEST_ROWS = 32
SERVE = {"requests": 8, "slots": 8, "prompt_len": 128, "max_new": 32}
SEED = 0
KERNEL_REL_TOL = 2e-2     # max |kernel - reference| over max |reference|:
                          # bfloat16 operands and MXU passes against a
                          # float32 reference at highest precision


def unigram_dataset_fn(n_train: int):
    """The CLI's ``dataset_fn`` plug-in: seeded tokens from one fixed
    skewed (Zipf) unigram distribution, so the loss has somewhere to fall —
    uniform tokens would start at the floor, ln V.  Next-token targets are
    materialized by the dataset, like ``data.loaders.synthetic_lm``."""
    from distributed_tensorflow_tpu.data import Dataset

    p = 1.0 / np.arange(1, VOCAB + 1)
    p /= p.sum()

    def dataset_fn(batch_size: int, type: str = "train", **_) -> Dataset:
        n = n_train if type == "train" else TEST_ROWS
        rng = np.random.default_rng((SEED, 0 if type == "train" else 1))
        tokens = rng.choice(VOCAB, size=(n, SEQ + 1), p=p).astype(np.int32)
        return Dataset(x=tokens[:, :-1], y=tokens[:, 1:], num_classes=VOCAB,
                       name="smoke_unigram", synthetic=True,
                       batch_size=batch_size)

    return dataset_fn


def cli_argv(metrics_path: Path, *serve_flags: str) -> list[str]:
    argv = ["-m", "t", "--model", "gpt", "--attention", "flash",
            "--dtype", "bf16", "-b", str(PER_CHIP_BATCH),
            "--seed", str(SEED), "--log-every", "1",
            "--metrics-path", str(metrics_path)]
    for key, value in WIDTH.items():
        argv += ["--model-arg", f"{key}={value}"]
    argv += ["--serve", str(SERVE["requests"]),
             "--serve-slots", str(SERVE["slots"]),
             "--serve-prompt-len", str(SERVE["prompt_len"]),
             "--serve-max-new", str(SERVE["max_new"]), *serve_flags]
    return argv


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def run_cli(workdir: Path, tag: str, dataset_fn, *serve_flags: str) -> dict:
    """One ``cli.main`` run: train, then the serve window on the trained
    params in the same process.  Returns the summary plus the per-step
    losses read back from ``--metrics-path``."""
    from distributed_tensorflow_tpu import cli

    metrics_path = workdir / f"metrics_{tag}.jsonl"
    t0 = time.perf_counter()
    summary = cli.main(cli_argv(metrics_path, *serve_flags),
                       dataset_fn=dataset_fn)
    summary["call_s"] = time.perf_counter() - t0
    records = [json.loads(line)
               for line in metrics_path.read_text().splitlines()]
    summary["losses"] = [float(r["loss"]) for r in records if "loss" in r]
    return summary


def check_train(summary: dict, devices) -> dict:
    losses = summary["losses"]
    require(summary["platform"] == "tpu",
            f"summary says platform {summary['platform']!r}")
    require(summary["n_devices"] == len(devices),
            f"trained on {summary['n_devices']} of {len(devices)} devices")
    require(summary["steps"] >= TRAIN_STEPS,
            f"{summary['steps']} steps < {TRAIN_STEPS}")
    require(len(losses) >= TRAIN_STEPS and all(map(math.isfinite, losses)),
            f"losses not finite or missing: {losses}")
    half = len(losses) // 2
    first, last = np.mean(losses[:half]), np.mean(losses[half:])
    require(last < first, f"loss did not fall: {first:.4f} -> {last:.4f}")
    require(math.isfinite(summary["test_loss"]), "test loss not finite")
    require(bool(summary.get("run_report")), "no run_report in the summary")
    return {"steps": summary["steps"], "loss_first_chunk": float(first),
            "loss_last_chunk": float(last),
            "test_loss": summary["test_loss"],
            "fit_s": summary["elapsed_s"],
            "input_pipeline": summary["input_pipeline"],
            "device_memory": check_memory(
                summary["run_report"]["device_memory"], len(devices))}


def check_memory(rows: list[dict], n_devices: int) -> list[dict]:
    """Every device of the run's mesh holds something now and held
    something at its peak: nothing sits on device 0 alone."""
    require(len(rows) == n_devices,
            f"memory rows for {len(rows)} of {n_devices} devices")
    require(all(r["bytes_in_use"] > 0 and r["peak_bytes_in_use"] > 0
                for r in rows), f"a device holds nothing: {rows}")
    return rows


def check_serve(summary: dict, n_devices: int) -> dict:
    serve = summary["serve"]
    want_tokens = SERVE["requests"] * SERVE["max_new"]
    require(serve["offered"] == SERVE["requests"]
            and serve["completed"] == SERVE["requests"],
            f"completed {serve['completed']} of {serve['offered']} offered")
    require(serve["tokens_generated"] == want_tokens,
            f"{serve['tokens_generated']} tokens, expected {want_tokens}")
    require(serve["unserved_requests"] == 0,
            f"{serve['unserved_requests']} requests unserved")
    # the CLI exits 0 on a degraded window; the smoke does not
    require(summary["serve_exit_policy"] == 0,
            "serve_exit_policy is not 0")
    return {"completed": serve["completed"],
            "tokens_generated": serve["tokens_generated"],
            "kv_dtype": serve["serve_kv_dtype"],
            "window_s": serve["elapsed_s"], "call_s": summary["call_s"],
            "device_memory": check_memory(serve["device_memory"],
                                          n_devices)}


def rel_err(got, ref) -> float:
    import jax.numpy as jnp

    ref = ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref))
                 / jnp.max(jnp.abs(ref)))


def mosaic_calls(jitted, *args) -> int:
    return jitted.lower(*args).as_text().count("tpu_custom_call")


def check_kernels(devices) -> dict:
    """Flash attention at the training shape against its jnp reference,
    on the chip.  A kernel that interpreted or gave way to its jnp twin
    has no Mosaic custom call in its lowered program and fails here."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.ops.flash_attention import (
        flash_attention)
    from distributed_tensorflow_tpu.parallel.ring_attention import (
        dense_attention, ring_flash_attention)

    heads, d = WIDTH["heads"], WIDTH["hidden"] // WIDTH["heads"]
    rng = np.random.default_rng(SEED)
    out: dict = {}

    def normal(shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    def highest(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*(a.astype(jnp.float32) for a in args))

    # flash forward and backward, (batch, seq, heads, head) of one chip
    q, k, v, w = (normal((PER_CHIP_BATCH, SEQ, heads, d)) for _ in range(4))

    def weighted(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=True).astype(jnp.float32) * w)

    fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    bwd = jax.jit(jax.grad(weighted(flash_attention), argnums=(0, 1, 2)))
    require(mosaic_calls(fwd, q, k, v) == 1, "flash forward is not Mosaic")
    require(mosaic_calls(bwd, q, k, v) == 3,
            "flash backward is not three Mosaic kernels (fwd, dq, dkv)")
    out["flash_fwd_rel_err"] = rel_err(
        fwd(q, k, v),
        highest(lambda q, k, v: dense_attention(q, k, v, causal=True),
                q, k, v))
    ref_grads = highest(
        jax.grad(weighted(dense_attention), argnums=(0, 1, 2)), q, k, v)
    out["flash_bwd_rel_err"] = max(
        rel_err(g, r) for g, r in zip(bwd(q, k, v), ref_grads))

    if len(devices) > 1:
        # the same kernel under shard_map with its varying axes declared:
        # the ring schedule over every local device
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.asarray(devices), ("seq",))
        spec = P(None, "seq", None, None)
        ring = jax.jit(jax.shard_map(
            lambda q, k, v: ring_flash_attention(q, k, v, axis="seq",
                                                 causal=True),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec))
        require(mosaic_calls(ring, q, k, v) >= 1,
                "ring_flash under shard_map is not Mosaic")
        out["ring_flash_rel_err"] = rel_err(
            ring(q, k, v),
            highest(lambda q, k, v: dense_attention(q, k, v, causal=True),
                    q, k, v))

    worst = max(v for key, v in out.items() if key.endswith("rel_err"))
    require(worst <= KERNEL_REL_TOL,
            f"a kernel is outside tolerance {KERNEL_REL_TOL}: {out}")
    out["tolerance"] = KERNEL_REL_TOL
    return out


def cache_entries(directory: str) -> int:
    path = Path(directory)
    if not path.is_dir():
        return 0
    return sum(1 for f in path.iterdir() if not f.name.endswith("-atime"))


def device_facts() -> dict:
    """The device as JAX reports it."""
    import jax

    first = jax.devices()[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(jax.devices())}


def verdict_line(ok: bool, device: dict) -> str:
    """The last line of standard output: the verdict and the device, one
    JSON object with exactly these keys (the driver reads it as such; the
    detail goes on the ``chip_smoke: report`` line before it)."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main() -> int:
    t_start = time.perf_counter()
    import jax

    # phase 1, before anything compiles: a TPU backend or nothing
    t0 = time.perf_counter()
    backend = jax.default_backend()
    devices = jax.local_devices()
    if backend != "tpu":
        print(f"chip_smoke: JAX found no TPU: default backend is "
              f"{backend!r} ({devices[0].device_kind} x{len(devices)}); "
              f"nothing was run", file=sys.stderr)
        return 2
    import jaxlib
    import libtpu

    from distributed_tensorflow_tpu.utils.harness import (
        resolve_compile_cache)

    device = device_facts()
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu.__version__}
    print(f"chip_smoke: {device} {versions}", flush=True)
    phases: dict = {"device": {"ok": True,
                               "s": round(time.perf_counter() - t0, 3)}}

    cache_dir = resolve_compile_cache()
    entries_before = cache_entries(cache_dir)

    def phase(name: str, fn) -> None:
        """Run one phase; a failure is recorded, the later phases still
        run, and the exit code reports it."""
        t0 = time.perf_counter()
        try:
            phases[name] = {"ok": True, **fn()}
        except Exception as e:
            traceback.print_exc()
            phases[name] = {"ok": False,
                            "error": f"{type(e).__name__}: {e}"[:500]}
        phases[name].setdefault("s", round(time.perf_counter() - t0, 3))
        print(f"chip_smoke: phase {name}: {phases[name]}", flush=True)

    dataset_fn = unigram_dataset_fn(
        TRAIN_STEPS * PER_CHIP_BATCH * len(devices))
    runs: dict = {}

    with tempfile.TemporaryDirectory() as tmp:
        workdir = Path(tmp)

        def train():
            # one CLI call trains and then serves the default-layout
            # window; the serve window's own wall time is phase 3's
            runs["default"] = run_cli(workdir, "default", dataset_fn)
            run = runs["default"]
            return {**check_train(run, devices),
                    "s": round(run["call_s"] - run["serve"]["elapsed_s"], 3)}

        def serve():
            return {**check_serve(runs["default"], len(devices)),
                    "s": round(runs["default"]["serve"]["elapsed_s"], 3)}

        phase("train", train)
        phase("serve", serve)
    phase("kernels", lambda: check_kernels(devices))
    phases["cache"] = {"ok": True, "dir": cache_dir,
                       "entries_before": entries_before,
                       "entries_after": cache_entries(cache_dir), "s": 0.0}

    ok = all(p["ok"] for p in phases.values())
    print("chip_smoke: report " + json.dumps({
        "ok": ok, "device": device, "versions": versions,
        "config": {**WIDTH, "vocab": VOCAB, "seq": SEQ, "dtype": "bfloat16",
                   "per_chip_batch": PER_CHIP_BATCH, **SERVE},
        "phases": phases,
        "total_s": round(time.perf_counter() - t_start, 3)}))
    print(verdict_line(ok, device), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
