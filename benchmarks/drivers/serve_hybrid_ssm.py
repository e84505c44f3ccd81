"""``drivers/serve.py``'s window for the hybrid state-space / attention /
latent-expert decoder: the same ``ContinuousBatcher.run`` over a
``SlotKVCache``, the same traffic generator and limits; what differs is the
model, and that a slot now keeps recurrent state beside its rows.

* The model is built BEFORE the weights: a program that does not know
  ``hybrid_ssm`` fails at once, not after 9.3 GB were made.
* Weights: ``lib/hybrid_ssm_weights.py``, bfloat16, made once and kept for
  the comparison: the program's tree is these very arrays re-labelled
  (``drivers/hybrid_ssm_tree.py``), so 9.3 GB are held once.
* ``max_len`` is the cell's (``job.max_len``), not the model's 262,144
  positions.
* ``model_flops`` come from ``lib/hybrid_ssm_costs.py`` with the experts
  HELD here that a token reached, read off the table's own counter
  (``expert_assignments`` over the tokens fed and the expert layers); the
  window also reports the mean context behind a decoded token and the
  table's two byte counts, for the decode round's memory roofline.
* The sample always holds the longest finished request and at least one
  that was admitted into a slot an earlier request of the window had held
  (the ``prefill`` span's ``slot``): what a state kept across occupants
  would spoil.
* The comparison runs ``lib/hybrid_ssm_reference.py`` over each sampled
  request once, padded to ``check.pad_to`` (one shape to compile), and
  raises the logits of the served positions only (``check.pad_new`` rows
  from the prompt's last position on).  Positions whose expert choice is a
  near-tie in the reference (``check.near_tie_margin``) are set apart and
  counted.  ``gaps(sample, mode=..., fault=...)`` gives the control's and a
  planted fault's reading; the faults a slot table can commit are planted
  at the request's own prompt length and bucket."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import hybrid_ssm_tree, serve
from benchmarks.lib import (hybrid_ssm_costs, hybrid_ssm_reference,
                            hybrid_ssm_weights, program_spans)


class Run(serve.Run):
    def __init__(self, cell: dict, config: dict, *, seed: int, seconds: float,
                 devices, note=print):
        self.cell, self.config, self.job = cell, config, cell["job"]
        self.mix = cell["traffic"]
        self.seed, self.seconds = int(seed), float(seconds)
        self.devices, self.note = list(devices), note
        self.vocab = int(config["vocab_size"])
        self.max_len = int(self.job["max_len"])
        self.dims = hybrid_ssm_reference.dims_of(config)
        self.weights = None

    # ------------------------------------------------------------ set-up
    def build(self) -> None:
        from distributed_tensorflow_tpu.models import create_model
        from distributed_tensorflow_tpu.parallel import mesh as meshlib
        from distributed_tensorflow_tpu.serving.kv_cache import SlotKVCache
        from distributed_tensorflow_tpu.serving.scheduler import (
            ContinuousBatcher)

        model = create_model(
            "hybrid_ssm", dtype=self.job["dtype"],
            param_dtype=self.job["param_dtype"],
            **hybrid_ssm_tree.model_kwargs(self.config, self.max_len))
        mesh = None if len(self.devices) == 1 else meshlib.create_mesh(
            devices=self.devices)
        self.weights = hybrid_ssm_weights.make(self.config, self.seed)
        self.kv = SlotKVCache(model, hybrid_ssm_tree.to_flax(self.weights),
                              int(self.job["slots"]), mesh=mesh, greedy=True,
                              kv_dtype=jnp.dtype(self.job["kv_dtype"]))
        self.batcher = ContinuousBatcher(self.kv)
        self.bucket_floor = int(self.kv.prefill_bucket)

    # ------------------------------------------------------------ window
    def serve(self, trace: list[dict]) -> dict:
        before = self.kv.counters()
        summary = self.batcher.run(self.requests(trace))
        counts = self.kv.counters()
        results = {r.rid: r for r in summary["results"]}
        done = [r for r in trace
                if r["rid"] in results
                and len(results[r["rid"]].tokens) == r["max_new_tokens"]]
        served = [results[r["rid"]] for r in done]
        window_s = max((r.finished_s for r in served), default=0.0) \
            - trace[0]["arrival_s"]
        missing = [3600.0] * (len(trace) - len(done))   # failed: an hour
        self.finished = [(r, results[r["rid"]].tokens) for r in done]
        # which slot each request was admitted into, in order of admission
        self.slots_held = [
            (r["rid"], r["attrs"]["slot"]) for r in program_spans.named(
                program_spans.window({"root": "serve_run"}), "prefill")
            if r["attrs"].get("slot") is not None]
        # a decoded token at position p has p tokens behind it
        decoded = [(r.prompt_len, len(r.tokens) - 1) for r in served]
        steps = sum(n for _, n in decoded)
        behind = sum(n * lp + n * (n - 1) / 2.0 for lp, n in decoded)
        # the held experts a token reached in an expert layer, on average
        fed = sum(lp + n for lp, n in decoded)
        held = (counts["expert_assignments"] - before["expert_assignments"]) \
            / max(fed * self.config["hybrid_override_pattern"].count("E"), 1)
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
            "model_flops": sum(hybrid_ssm_costs.serve_flops(
                self.config, r.prompt_len, len(r.tokens), held)
                for r in served),
            "held_experts_per_token": held,
            "decode_context_mean": behind / steps if steps else None,
            "cache_bytes_per_token": counts["cache_bytes_per_token"],
            "state_bytes_per_slot": counts["state_bytes_per_slot"],
        }

    # ------------------------------------------------------------- check
    def check(self, obs: dict) -> list[dict]:
        if not self.finished:       # nothing to judge: every limit is missed
            return [{"name": name, "value": 1e9, "limit": limit}
                    for name, limit in self.cell["limits"].items()]
        return super().check(obs)

    def sample(self) -> list[tuple[dict, list[int]]]:
        """``drivers/serve.py``'s sample (drawn from the seed, the longest
        among them), with one of the requests that were admitted into a
        slot an earlier request of the window had held always in it."""
        n = int(self.cell["check"]["sample_requests"])
        rng = np.random.default_rng([self.seed, 4])
        order = [int(i) for i in rng.permutation(len(self.finished))]
        longest = max(range(len(self.finished)), key=lambda i: len(
            self.finished[i][0]["prompt"]) + len(self.finished[i][1]))
        seen, reused = set(), set()
        for rid, slot in self.slots_held:
            if slot in seen:
                reused.add(rid)
            seen.add(slot)
        in_reused = [i for i in order
                     if self.finished[i][0]["rid"] in reused]
        picked = [longest]
        if in_reused and self.finished[longest][0]["rid"] not in reused:
            picked.append(in_reused[0])
        picked += [i for i in order if i not in picked][:max(n - len(picked),
                                                             0)]
        self.sampled_reused = sum(
            self.finished[i][0]["rid"] in reused for i in picked)
        return [self.finished[i] for i in picked]

    def _sequence(self, req, toks):
        """The request as the reference reads it: prompt and served tokens
        but the last, padded to the one compiled length."""
        pad, pad_new = (int(self.cell["check"][k])
                        for k in ("pad_to", "pad_new"))
        lp, n = len(req["prompt"]), len(req["prompt"]) + len(toks) - 1
        seq = np.zeros(pad * -(-(lp - 1 + pad_new) // pad), np.int32)
        seq[:lp], seq[lp:n] = req["prompt"], toks[:-1]
        return jnp.asarray(seq)

    def reference_rows(self, sample) -> list[tuple]:
        """For each sampled request, the reference's logits at the served
        positions (``check.pad_new`` rows from the prompt's last position
        on) and those positions' least choice margin: one pass of the
        reference a request, kept while the same sample is judged again
        (the control and the faults of a calibration)."""
        key = tuple(req["rid"] for req, _ in sample)
        if getattr(self, "_rows", (None,))[0] == key:
            return self._rows[1]
        pad_new = int(self.cell["check"]["pad_new"])

        @jax.jit
        def rows(w, seq, first):
            hidden, margin = hybrid_ssm_reference.hidden_fn(
                w, seq, self.dims, margins=True)
            at = lambda t: jax.lax.dynamic_slice_in_dim(t, first, pad_new, 0)
            return hybrid_ssm_reference.head_fn(w, at(hidden)), at(margin)

        out = []
        for req, toks in sample:
            seq = self._sequence(req, toks)
            out.append((seq, *rows(self.weights, seq,
                                   len(req["prompt"]) - 1)))
        self._rows = (key, out)
        return out

    def gaps(self, sample, mode: str = "f32",
             fault: str | None = None) -> dict[str, float]:
        """As ``drivers/serve_mla_moe.py``'s: the widest gap by which a
        served token's logit lies below the reference's best at its
        position; with a ``mode`` or a ``fault``, the token judged is the
        one that variant of the reference puts first there.  Positions at
        which some expert layer's last chosen and first unchosen expert lie
        closer in the reference than ``check.near_tie_margin`` are left out
        of the gap and counted in ``near_tie_share``.  ``self.judged``
        keeps every served position's gap and margin for a calibration to
        read."""
        pad_new = int(self.cell["check"]["pad_new"])
        tie = float(self.cell["check"].get("near_tie_margin", 0.0))

        @jax.jit
        def chosen(w, seq, first, prompt_len, pads):
            hidden = hybrid_ssm_reference.hidden_fn(
                w, seq, self.dims, mode=mode, fault=fault,
                prompt_len=prompt_len, pads=pads)
            return jnp.argmax(hybrid_ssm_reference.head_fn(
                w, jax.lax.dynamic_slice_in_dim(hidden, first, pad_new, 0),
                mode=mode), axis=-1)

        @jax.jit
        def below(logits, served):
            return jnp.max(logits, -1) - jnp.take_along_axis(
                logits, served[:, None], 1)[:, 0]

        gap, margin = [], []
        for (req, toks), (seq, logits, least) in zip(
                sample, self.reference_rows(sample)):
            lp, new = len(req["prompt"]), len(toks)
            if mode != "f32" or fault is not None:
                bucket = max(self.bucket_floor, 1 << (lp - 1).bit_length())
                served = chosen(self.weights, seq, lp - 1, lp, bucket - lp)
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
