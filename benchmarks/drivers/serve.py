"""Drives ``serving/scheduler.ContinuousBatcher.run`` over a ``SlotKVCache``
for a timed window of open-loop traffic, and compares a sample of what it
served with the plain reference.

The cell's file gives the server (``job``: slots, table dtype, model
dtype), the traffic mix (``traffic``, read by ``lib/traffic.request_trace``)
and the comparison (``check``, ``limits``).  Every offered request is served
to completion; the window is the first due time to the last completion.
Set-up serves one untimed request per prompt length of ``warm_prompt_lens``
(one per prefill program the mix can reach) and so warms the decode step
too.  With more than one device the slot table is sharded over a 1-D
``data`` mesh of all of them."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import gpt_tree
from benchmarks.lib import costs, reference, traffic, weights

TRACE_LEAD_S = 0.1      # the profiler is running when the prompt falls due


class Run:
    def __init__(self, cell: dict, config: dict, *, seed: int, seconds: float,
                 devices, note=print):
        self.cell, self.config, self.job = cell, config, cell["job"]
        self.mix = cell["traffic"]
        self.seed, self.seconds = int(seed), float(seconds)
        self.devices, self.note = list(devices), note
        self.vocab = int(config["vocab_size"])
        self.max_len = int(config["n_positions"])
        self.ref_kw = dict(heads=int(config["n_head"]),
                           eps=float(config["layer_norm_epsilon"]))

    # ------------------------------------------------------------ set-up
    def build(self) -> None:
        from distributed_tensorflow_tpu.models import create_model
        from distributed_tensorflow_tpu.parallel import mesh as meshlib
        from distributed_tensorflow_tpu.serving.kv_cache import SlotKVCache
        from distributed_tensorflow_tpu.serving.scheduler import (
            ContinuousBatcher)

        mesh = None if len(self.devices) == 1 else meshlib.create_mesh(
            devices=self.devices)
        model = create_model("gpt", dtype=self.job["dtype"],
                             **gpt_tree.model_kwargs(self.config))
        params = jax.jit(gpt_tree.to_flax)(
            weights.make(self.config, self.seed))
        self.kv = SlotKVCache(model, params, int(self.job["slots"]),
                              mesh=mesh, greedy=True,
                              kv_dtype=jnp.dtype(self.job["kv_dtype"]))
        self.batcher = ContinuousBatcher(self.kv)

    def requests(self, trace: list[dict]):
        from distributed_tensorflow_tpu.serving.scheduler import Request

        return [Request(rid=r["rid"], prompt=r["prompt"],
                        max_new_tokens=r["max_new_tokens"],
                        arrival_s=r["arrival_s"]) for r in trace]

    def warm(self) -> None:
        rng = np.random.default_rng([self.seed, 3])
        new = int(self.job.get("warm_new_tokens", 4))
        trace = [{"rid": i, "arrival_s": 0.0, "max_new_tokens": new,
                  "prompt": rng.integers(0, self.vocab, int(lp),
                                         dtype=np.int32)}
                 for i, lp in enumerate(self.job["warm_prompt_lens"])]
        self.batcher.run(self.requests(trace))

    def setup(self) -> None:
        self.build()
        self.note("weights made, slot table built; warming")
        self.warm()
        self.trace = traffic.request_trace(self.seed, self.mix, self.seconds,
                                           self.vocab, self.max_len)

    # ------------------------------------------------------------ window
    def trace_slice(self) -> tuple[float, float]:
        """The traced slice follows an arrival, so that it holds a prefill
        beside decode rounds: it starts ``TRACE_LEAD_S`` before the due
        time of the longest prompt that is due in the middle half of the
        window (the trace is known before the window opens)."""
        mid = [r for r in self.trace
               if 0.25 * self.seconds <= r["arrival_s"] <= 0.75 * self.seconds]
        at = max(mid or self.trace, key=lambda r: len(r["prompt"]))
        return (max(0.0, at["arrival_s"] - TRACE_LEAD_S),
                float(self.job.get("trace_seconds", 5.0)))

    def serve(self, trace: list[dict]) -> dict:
        """One window over ``trace``; what was observed, under the
        driver's own keys."""
        summary = self.batcher.run(self.requests(trace))
        results = {r.rid: r for r in summary["results"]}
        done = [r for r in trace
                if r["rid"] in results
                and len(results[r["rid"]].tokens) == r["max_new_tokens"]]
        served = [results[r["rid"]] for r in done]
        window_s = max((r.finished_s for r in served), default=0.0) \
            - trace[0]["arrival_s"]
        # a request that failed counts as the worst: an hour
        missing = [3600.0] * (len(trace) - len(done))
        self.finished = [(r, results[r["rid"]].tokens) for r in done]
        return {
            "attempted": len(trace), "failed": len(trace) - len(done),
            "window_s": window_s,
            "tokens": sum(len(r.tokens) for r in served),
            "ttft_s": [r.ttft_s for r in served] + missing,
            "itl_s": [g for r in served for g in r.itl_s],
            "queue_wait_s": [r.queue_wait_s for r in served] + missing,
            # slot claim to first token, a prompt token: the prefill scan
            "prefill_s_per_token": [(r.ttft_s - r.queue_wait_s) / r.prompt_len
                                    for r in served],
            "drain_s": window_s - trace[-1]["arrival_s"],
            "model_flops": sum(costs.serve_flops(
                self.config, r.prompt_len, len(r.tokens)) for r in served),
        }

    def window(self) -> dict:
        return self.serve(self.trace)

    # ------------------------------------------------------------- check
    def free(self) -> None:
        self.kv = self.batcher = None
        jax.clear_caches()

    def sample(self) -> list[tuple[dict, list[int]]]:
        """Finished requests drawn from the seed, the longest among them."""
        n = int(self.cell["check"]["sample_requests"])
        rng = np.random.default_rng([self.seed, 4])
        order = list(rng.permutation(len(self.finished)))
        longest = max(range(len(self.finished)), key=lambda i: len(
            self.finished[i][0]["prompt"]) + len(self.finished[i][1]))
        picked = [longest] + [i for i in order if i != longest][:n - 1]
        return [self.finished[i] for i in picked]

    def gaps(self, sample, mode: str = "f32") -> dict[str, float]:
        """The widest gap, over every served token of the sample, by which
        its logit lies below the reference's best at its position.  With
        another ``mode`` the token judged is the one that precision puts
        first there (the control)."""
        pad = int(self.cell["check"]["pad_to"])

        @jax.jit
        def gap(w, seq, served, first, count):
            logits = reference.logits_fn(w, seq, **self.ref_kw)
            if mode != "f32":
                served_at = jnp.argmax(reference.logits_fn(
                    w, seq, mode=mode, **self.ref_kw), axis=-1)
            else:   # served[i] was sampled from position first + i
                served_at = jnp.zeros(seq.shape, jnp.int32).at[
                    first + jnp.arange(served.shape[0])].set(
                        served, mode="drop")
            pos = jnp.arange(seq.shape[0])
            live = (pos >= first) & (pos < first + count)
            below = jnp.max(logits, -1) - jnp.take_along_axis(
                logits, served_at[:, None], 1)[:, 0]
            return jnp.max(jnp.where(live, below, 0.0))

        w = weights.make(self.config, self.seed)
        worst, tokens = 0.0, 0
        for req, toks in sample:
            lp, new = len(req["prompt"]), len(toks)
            n = lp + new - 1
            size = pad * -(-n // pad)
            seq = np.zeros(size, np.int32)
            seq[:lp], seq[lp:n] = req["prompt"], toks[:-1]
            served = np.zeros(size, np.int32)
            served[:new] = toks
            worst = max(worst, float(gap(
                w, jnp.asarray(seq), jnp.asarray(served), lp - 1, new)))
            tokens += new
        return {"token_logit_gap": worst, "tokens_compared": tokens}

    def check(self, obs: dict) -> list[dict]:
        sample = self.sample() if self.finished else []
        self.free()
        t0 = time.perf_counter()
        readings = self.gaps(sample) if sample else {
            "token_logit_gap": 1e9}
        self.note(f"reference read {readings.get('tokens_compared', 0)} "
                  f"served tokens of {len(sample)} requests in "
                  f"{time.perf_counter() - t0:.1f} s")
        readings["requests_failed"] = obs["failed"]
        limits = self.cell["limits"]
        return [{"name": name, "value": readings[name], "limit": limits[name]}
                for name in limits]
