"""``drivers/serve.py``'s window for the window-and-global-attention,
sparse-expert decoder: the same ``ContinuousBatcher.run`` over a
``SlotKVCache``, the same traffic generator and limits; what differs is the
model, and that a slot now keeps rings of its last positions beside its
full-length rows.

* The model is built BEFORE the weights: a program that does not know
  ``window_moe`` fails at once, not after 9.5 GB were made.
* Weights: ``lib/window_moe_weights.py``, bfloat16, made once and kept for
  the comparison: the program's tree is these very arrays re-labelled
  (``drivers/window_moe_tree.py``), so 9.5 GB are held once.
* ``max_len`` is the cell's (``job.max_len``), not the model's 200,000
  positions.
* ``model_flops`` come from ``lib/window_moe_costs.py`` with the experts
  HELD here that a token reached, read off the table's own counter
  (``expert_assignments`` over the tokens fed and the layers); the window
  also reports the mean context behind a decoded token, that mean over the
  tokens whose context still fits the ring, and the table's byte counts,
  for the decode round's memory roofline.
* The sample always holds the longest finished request (in the cell's
  traffic its context passes the window) and, where the window has one, a
  request that was admitted into a slot whose last occupant was longer
  than it and longer than the window (the ``prefill`` span's ``slot``; of
  those the one with the shortest prompt): what a ring kept across
  occupants would spoil.
* The comparison runs ``lib/window_moe_reference.py`` over each sampled
  request once, padded to ``check.pad_to`` (one shape to compile), and
  raises the logits of the served positions only (``check.pad_new`` rows
  from the prompt's last position on).  Positions whose expert choice is a
  near-tie in the reference (``check.near_tie_margin``) are set apart and
  counted.  ``gaps(sample, mode=..., fault=...)`` gives the control's and a
  planted fault's reading; the faults a prefill bucket can commit are
  planted at the request's own prompt length and bucket."""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import serve, window_moe_tree
from benchmarks.lib import (program_spans, window_moe_costs,
                            window_moe_reference, window_moe_weights)


class Run(serve.Run):
    def __init__(self, cell: dict, config: dict, *, seed: int, seconds: float,
                 devices, note=print):
        self.cell, self.config, self.job = cell, config, cell["job"]
        self.mix = cell["traffic"]
        self.seed, self.seconds = int(seed), float(seconds)
        self.devices, self.note = list(devices), note
        self.vocab = int(config["vocab_size"])
        self.max_len = int(self.job["max_len"])
        self.dims = window_moe_reference.dims_of(config)
        self.ring = min(self.dims["window"], self.max_len)
        self.weights = None

    # ------------------------------------------------------------ set-up
    def build(self) -> None:
        from distributed_tensorflow_tpu.models import create_model
        from distributed_tensorflow_tpu.parallel import mesh as meshlib
        from distributed_tensorflow_tpu.serving.kv_cache import SlotKVCache
        from distributed_tensorflow_tpu.serving.scheduler import (
            ContinuousBatcher)

        model = create_model(
            "window_moe", dtype=self.job["dtype"],
            param_dtype=self.job["param_dtype"],
            **window_moe_tree.model_kwargs(self.config, self.max_len))
        mesh = None if len(self.devices) == 1 else meshlib.create_mesh(
            devices=self.devices)
        self.weights = window_moe_weights.make(self.config, self.seed)
        self.kv = SlotKVCache(model, window_moe_tree.to_flax(self.weights),
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
        # a decoded token at position p attends p + 1 rows of a full layer
        # and min(p + 1, ring) of a ring
        at = np.concatenate([r.prompt_len + np.arange(len(r.tokens) - 1)
                             for r in served] or [np.zeros(0, int)])
        below = at[at <= self.ring]
        # the held experts a token reached in a layer, on average
        fed = sum(r.prompt_len + len(r.tokens) - 1 for r in served)
        held = (counts["expert_assignments"] - before["expert_assignments"]) \
            / max(fed * len(self.dims["windowed"]), 1)
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
            "model_flops": sum(window_moe_costs.serve_flops(
                self.config, r.prompt_len, len(r.tokens), held)
                for r in served),
            "held_experts_per_token": held,
            "decode_context_mean": float(at.mean() + 1) if at.size else None,
            "decode_context_mean_below_window":
                float(below.mean() + 1) if below.size
                else float(self.ring) if at.size else None,
            "cache_bytes_per_token": counts["cache_bytes_per_token"],
            "window_bytes_per_slot": counts["window_bytes_per_slot"],
            "ring_rows": self.ring,
        }

    # ------------------------------------------------------------- check
    def free(self) -> None:
        """The table and its programs refer to each other: only a
        collection gives the table's 3.76 GB back to the reference."""
        super().free()
        gc.collect()

    def check(self, obs: dict) -> list[dict]:
        if not self.finished:       # nothing to judge: every limit is missed
            return [{"name": name, "value": 1e9, "limit": limit}
                    for name, limit in self.cell["limits"].items()]
        return super().check(obs)

    def sample(self) -> list[tuple[dict, list[int]]]:
        """``drivers/serve.py``'s sample (drawn from the seed, the longest
        among them), with one of the requests that took the slot of a
        longer occupant whose context had passed the window always in it,
        where the window has one."""
        n = int(self.cell["check"]["sample_requests"])
        rng = np.random.default_rng([self.seed, 4])
        order = [int(i) for i in rng.permutation(len(self.finished))]
        total = {req["rid"]: len(req["prompt"]) + len(toks)
                 for req, toks in self.finished}
        longest = max(range(len(self.finished)),
                      key=lambda i: total[self.finished[i][0]["rid"]])
        last, after_longer = {}, set()
        for rid, slot in self.slots_held:
            if total.get(last.get(slot), 0) > max(self.ring,
                                                  total.get(rid, 0)):
                after_longer.add(rid)
            last[slot] = rid
        rid_of = lambda i: self.finished[i][0]["rid"]
        # of those, the one with the shortest prompt: the most stale rows
        picked = [longest] + sorted(
            (i for i in order if rid_of(i) in after_longer and i != longest),
            key=lambda i: len(self.finished[i][0]["prompt"]))[:1]
        picked += [i for i in order if i not in picked][:max(n - len(picked),
                                                             0)]
        self.sampled_after_longer = sum(rid_of(i) in after_longer
                                        for i in picked)
        self.sampled_past_window = sum(total[rid_of(i)] > self.ring
                                       for i in picked)
        self.note(f"sample of {len(picked)}: {self.sampled_past_window} "
                  f"past the window, {self.sampled_after_longer} after a "
                  f"longer occupant ({len(after_longer)} such served)")
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
            hidden, margin = window_moe_reference.hidden_fn(
                w, seq, self.dims, margins=True)
            at = lambda t: jax.lax.dynamic_slice_in_dim(t, first, pad_new, 0)
            return (window_moe_reference.head_fn(w, at(hidden), self.dims),
                    at(margin))

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
        which some layer's last chosen and first unchosen expert lie closer
        in the reference than ``check.near_tie_margin`` are left out of the
        gap and counted in ``near_tie_share``.  ``self.judged`` keeps every
        served position's gap and margin for a calibration to read."""
        pad_new = int(self.cell["check"]["pad_new"])
        tie = float(self.cell["check"].get("near_tie_margin", 0.0))

        @jax.jit
        def chosen(w, seq, first, prompt_len, pads):
            hidden = window_moe_reference.hidden_fn(
                w, seq, self.dims, mode=mode, fault=fault,
                prompt_len=prompt_len, pads=pads)
            return jnp.argmax(window_moe_reference.head_fn(
                w, jax.lax.dynamic_slice_in_dim(hidden, first, pad_new, 0),
                self.dims, mode=mode), axis=-1)

        @jax.jit
        def below(logits, served):
            return jnp.max(logits, -1) - jnp.take_along_axis(
                logits, served[:, None], 1)[:, 0]

        gap, margin = [], []
        for (req, toks), (seq, logits, least) in zip(
                sample, self.reference_rows(sample)):
            lp, new = len(req["prompt"]), len(toks)
            if mode != "f32" or fault is not None:
                bucket = min(self.max_len, max(
                    self.bucket_floor, 1 << (lp - 1).bit_length()))
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
