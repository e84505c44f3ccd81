"""``drivers/serve.py``'s window for the latent-attention, sparse-expert
decoder: the same ``ContinuousBatcher.run`` over a ``SlotKVCache``, the
same traffic generator, sample and limits; what differs is the model.

* Weights: ``lib/mla_moe_weights.py``, bfloat16, made once and kept for the
  comparison: the program's tree is these very arrays re-labelled
  (``drivers/mla_moe_tree.py``), so 7.6 GB are held once.
* ``max_len`` is the cell's (``job.max_len``), not the model's 32k
  positions; the table is ``slots x max_len`` latents.
* ``model_flops`` come from ``lib/mla_moe_costs.py`` (active parameters,
  expanded prefill, absorbed decode); the window also reports the mean
  context behind a decoded token and the table's bytes a token, for the
  decode round's memory roofline.
* The comparison runs ``lib/mla_moe_reference.py`` over each sampled
  request once, and raises the logits of the served positions only (a
  block of ``check.pad_new`` rows of the 128k-wide head, not 8k of them).
  Positions whose expert choice is a near-tie in the reference
  (``check.near_tie_margin``) are set apart and counted.
  ``gaps(sample, mode=..., fault=...)`` gives the control's and a planted
  fault's reading.
* The traced slice opens as ``drivers/serve.py``'s does and lasts
  ``job.trace_seconds``: the prefill it opens on and the rounds after."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import mla_moe_tree, serve
from benchmarks.lib import mla_moe_costs, mla_moe_reference, mla_moe_weights


class Run(serve.Run):
    def __init__(self, cell: dict, config: dict, *, seed: int, seconds: float,
                 devices, note=print):
        self.cell, self.config, self.job = cell, config, cell["job"]
        self.mix = cell["traffic"]
        self.seed, self.seconds = int(seed), float(seconds)
        self.devices, self.note = list(devices), note
        self.vocab = int(config["vocab_size"])
        self.max_len = int(self.job["max_len"])
        self.dims = mla_moe_reference.dims_of(config)
        self.weights = None

    # ------------------------------------------------------------ set-up
    def build(self) -> None:
        from distributed_tensorflow_tpu.models import create_model
        from distributed_tensorflow_tpu.parallel import mesh as meshlib
        from distributed_tensorflow_tpu.serving.kv_cache import SlotKVCache
        from distributed_tensorflow_tpu.serving.scheduler import (
            ContinuousBatcher)

        mesh = None if len(self.devices) == 1 else meshlib.create_mesh(
            devices=self.devices)
        model = create_model(
            "mla_moe", dtype=self.job["dtype"],
            param_dtype=self.job["param_dtype"],
            **mla_moe_tree.model_kwargs(self.config, self.max_len))
        self.weights = mla_moe_weights.make(self.config, self.seed)
        self.kv = SlotKVCache(model, mla_moe_tree.to_flax(self.weights),
                              int(self.job["slots"]), mesh=mesh, greedy=True,
                              kv_dtype=jnp.dtype(self.job["kv_dtype"]))
        self.batcher = ContinuousBatcher(self.kv)

    # ------------------------------------------------------------ window
    def serve(self, trace: list[dict]) -> dict:
        summary = self.batcher.run(self.requests(trace))
        results = {r.rid: r for r in summary["results"]}
        done = [r for r in trace
                if r["rid"] in results
                and len(results[r["rid"]].tokens) == r["max_new_tokens"]]
        served = [results[r["rid"]] for r in done]
        window_s = max((r.finished_s for r in served), default=0.0) \
            - trace[0]["arrival_s"]
        missing = [3600.0] * (len(trace) - len(done))   # failed: an hour
        self.finished = [(r, results[r["rid"]].tokens) for r in done]
        # a decoded token at position p has p tokens behind it
        decoded = [(r.prompt_len, len(r.tokens) - 1) for r in served]
        steps = sum(n for _, n in decoded)
        behind = sum(n * lp + n * (n - 1) / 2.0 for lp, n in decoded)
        return {
            "attempted": len(trace), "failed": len(trace) - len(done),
            "window_s": window_s,
            "tokens": sum(len(r.tokens) for r in served),
            "ttft_s": [r.ttft_s for r in served] + missing,
            "itl_s": [g for r in served for g in r.itl_s],
            "queue_wait_s": [r.queue_wait_s for r in served] + missing,
            "prefill_s_per_token": [(r.ttft_s - r.queue_wait_s) / r.prompt_len
                                    for r in served],
            "drain_s": window_s - trace[-1]["arrival_s"],
            "model_flops": sum(mla_moe_costs.serve_flops(
                self.config, r.prompt_len, len(r.tokens)) for r in served),
            "decode_context_mean": behind / steps if steps else None,
            "cache_bytes_per_token":
                self.kv.counters()["cache_bytes_per_token"],
        }

    # ------------------------------------------------------------- check
    def check(self, obs: dict) -> list[dict]:
        if not self.finished:       # nothing to judge: every limit is missed
            return [{"name": name, "value": 1e9, "limit": limit}
                    for name, limit in self.cell["limits"].items()]
        return super().check(obs)

    def reference_rows(self, sample) -> list[tuple]:
        """For each sampled request, the reference's logits at the served
        positions (``check.pad_new`` rows from the prompt's last position
        on) and those positions' least choice margin: one pass of the
        reference a request, kept while the same sample is judged again
        (the control and the faults of a calibration)."""
        key = tuple(req["rid"] for req, _ in sample)
        if getattr(self, "_rows", (None,))[0] == key:
            return self._rows[1]
        pad, pad_new = (int(self.cell["check"][k])
                        for k in ("pad_to", "pad_new"))

        @jax.jit
        def rows(w, seq, first):
            hidden, margin = mla_moe_reference.hidden_fn(
                w, seq, self.dims, margins=True)
            at = lambda t: jax.lax.dynamic_slice_in_dim(t, first, pad_new, 0)
            return mla_moe_reference.head_fn(w, at(hidden)), at(margin)

        out = []
        for req, toks in sample:
            lp, n = len(req["prompt"]), len(req["prompt"]) + len(toks) - 1
            # room for pad_new rows from the prompt's last position on
            seq = np.zeros(pad * -(-(lp - 1 + pad_new) // pad), np.int32)
            seq[:lp], seq[lp:n] = req["prompt"], toks[:-1]
            seq = jnp.asarray(seq)
            out.append((seq, *rows(self.weights, seq, lp - 1)))
        self._rows = (key, out)
        return out

    def gaps(self, sample, mode: str = "f32",
             fault: str | None = None) -> dict[str, float]:
        """As ``drivers/serve.py``'s: the widest gap by which a served
        token's logit lies below the reference's best at its position; with
        a ``mode`` or a ``fault``, the token judged is the one that variant
        of the reference puts first there.

        Positions at which some expert layer's last chosen and first
        unchosen expert lie closer in the reference than
        ``check.near_tie_margin`` are set apart (bfloat16 chooses another
        expert there and the logit moves by a step, whatever computed it):
        they are left out of the gap and counted in ``near_tie_share``,
        which has a limit of its own.  ``self.judged`` keeps every served
        position's gap and margin for a calibration to read."""
        pad_new = int(self.cell["check"]["pad_new"])
        tie = float(self.cell["check"].get("near_tie_margin", 0.0))

        @jax.jit
        def chosen(w, seq, first):
            hidden = mla_moe_reference.hidden_fn(w, seq, self.dims,
                                                 mode=mode, fault=fault)
            return jnp.argmax(mla_moe_reference.head_fn(
                w, jax.lax.dynamic_slice_in_dim(hidden, first, pad_new, 0),
                mode=mode), axis=-1)

        @jax.jit
        def below(logits, served):
            return jnp.max(logits, -1) - jnp.take_along_axis(
                logits, served[:, None], 1)[:, 0]

        gap, margin = [], []
        for (req, toks), (seq, logits, least) in zip(
                sample, self.reference_rows(sample)):
            new = len(toks)
            if mode != "f32" or fault is not None:
                served = chosen(self.weights, seq, len(req["prompt"]) - 1)
            else:
                served = jnp.zeros(pad_new, jnp.int32).at[:new].set(
                    jnp.asarray(toks, jnp.int32))
            gap.append(np.asarray(below(logits, served))[:new])
            margin.append(np.asarray(least)[:new])
        gap, margin = np.concatenate(gap), np.concatenate(margin)
        self.judged = {"gap": gap, "margin": margin}
        clear = margin >= tie
        return {"token_logit_gap": float(gap[clear].max(initial=0.0)),
                "near_tie_share": float(1.0 - clear.mean()),
                "tokens_compared": int(clear.sum())}
