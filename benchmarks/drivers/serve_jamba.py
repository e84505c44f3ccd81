"""``drivers/serve.py``'s window for the selective-scan / multi-query-
attention hybrid decoder: the same ``ContinuousBatcher.run`` over a
``SlotKVCache``, the same traffic generator and limits; what differs is the
model, that a slot keeps a ``(d_inner, d_state)`` recurrent state and a
convolution tail beside one key/value head's rows, and that prompts run to
tens of thousands of tokens.

* The model is built BEFORE the weights: a program that does not know
  ``jamba`` fails at once, not after 6 GB were made.
* Weights: ``lib/jamba_weights.py``, bfloat16, made once and kept for the
  comparison: the program's tree is these very arrays re-labelled
  (``drivers/jamba_tree.py``), so 6.06 GB are held once.
* ``max_len`` is the cell's (``job.max_len``), not the model's 262,144
  positions.
* ``model_flops`` come from ``lib/jamba_costs.py`` (no expert is sparse:
  every token passes every parameter); the window also reports the mean
  context behind a decoded token, the table's two byte counts (for the
  decode round's memory roofline) and the table's two counts of what went
  through the selective-scan kernel.
* The sample always holds the longest finished request and, where the
  window has one, a request that was admitted into a slot whose last
  occupant of the window was longer than it (the ``prefill`` span's
  ``slot``; of those the one with the shortest prompt): what a state, a
  tail or rows kept across occupants would spoil.
* The comparison runs ``lib/jamba_reference.py`` over each sampled request
  once, layer by layer (each kind of layer one compiled program a length),
  padded to a multiple of ``check.pad_to``, and raises the logits of the
  served positions only (``check.pad_new`` rows from the prompt's last
  position on).  ``gaps(sample, mode=..., fault=...)`` gives the control's
  and a planted fault's reading; the faults a slot table can commit are
  planted at the request's own prompt length and bucket."""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import jamba_tree, serve
from benchmarks.lib import (jamba_costs, jamba_reference, jamba_weights,
                            program_spans)


class Run(serve.Run):
    def __init__(self, cell: dict, config: dict, *, seed: int, seconds: float,
                 devices, note=print):
        self.cell, self.config, self.job = cell, config, cell["job"]
        self.mix = cell["traffic"]
        self.seed, self.seconds = int(seed), float(seconds)
        self.devices, self.note = list(devices), note
        self.vocab = int(config["vocab_size"])
        self.max_len = int(self.job["max_len"])
        self.dims = jamba_reference.dims_of(config)
        self.weights = None

    # ------------------------------------------------------------ set-up
    def build(self) -> None:
        from distributed_tensorflow_tpu.models import create_model
        from distributed_tensorflow_tpu.parallel import mesh as meshlib
        from distributed_tensorflow_tpu.serving.kv_cache import SlotKVCache
        from distributed_tensorflow_tpu.serving.scheduler import (
            ContinuousBatcher)

        model = create_model(
            "jamba", dtype=self.job["dtype"],
            param_dtype=self.job["param_dtype"],
            **jamba_tree.model_kwargs(self.config, self.max_len))
        mesh = None if len(self.devices) == 1 else meshlib.create_mesh(
            devices=self.devices)
        self.weights = jamba_weights.make(self.config, self.seed)
        self.kv = SlotKVCache(model, jamba_tree.to_flax(self.weights),
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
        moved = lambda key: counts.get(key, 0) - before.get(key, 0)
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
            "model_flops": sum(jamba_costs.serve_flops(
                self.config, r.prompt_len, len(r.tokens)) for r in served),
            "decode_context_mean": behind / steps if steps else None,
            "cache_bytes_per_token": counts["cache_bytes_per_token"],
            "state_bytes_per_slot": counts["state_bytes_per_slot"],
            "ssm_scan_positions": moved("ssm_scan_positions"),
            "ssm_scan_tokens": moved("ssm_scan_tokens"),
        }

    # ------------------------------------------------------------- check
    def free(self) -> None:
        """The table and its programs refer to each other: only a
        collection gives the table's 1.4 GB and the programs' temporaries
        back to the reference."""
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
        longer occupant always in it, where the window has one."""
        n = int(self.cell["check"]["sample_requests"])
        rng = np.random.default_rng([self.seed, 4])
        order = [int(i) for i in rng.permutation(len(self.finished))]
        total = {req["rid"]: len(req["prompt"]) + len(toks)
                 for req, toks in self.finished}
        longest = max(range(len(self.finished)),
                      key=lambda i: total[self.finished[i][0]["rid"]])
        last, after_longer = {}, set()
        for rid, slot in self.slots_held:
            if total.get(last.get(slot), 0) > total.get(rid, 1 << 62):
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
        self.note(f"sample of {len(picked)}: "
                  f"{[total[rid_of(i)] for i in picked]} tokens, "
                  f"{self.sampled_after_longer} after a longer occupant "
                  f"({len(after_longer)} such served)")
        return [self.finished[i] for i in picked]

    def _sequence(self, req, toks):
        """The request as the reference reads it: prompt and served tokens
        but the last, padded to a multiple of ``check.pad_to``."""
        pad, pad_new = (int(self.cell["check"][k])
                        for k in ("pad_to", "pad_new"))
        lp, n = len(req["prompt"]), len(req["prompt"]) + len(toks) - 1
        seq = np.zeros(pad * -(-(lp - 1 + pad_new) // pad), np.int32)
        seq[:lp], seq[lp:n] = req["prompt"], toks[:-1]
        return jnp.asarray(seq)

    def _served_rows(self, seq, first, **kw):
        """The reference's logits at the ``check.pad_new`` positions from
        ``first`` on (one pass over ``seq``, layer by layer)."""
        pad_new = int(self.cell["check"]["pad_new"])
        hidden = jamba_reference.hidden_fn(self.weights, seq, self.dims, **kw)
        rows = jax.lax.dynamic_slice_in_dim(hidden, first, pad_new, 0)
        del hidden
        return jax.jit(jamba_reference.head_fn, static_argnames="mode")(
            self.weights, rows, mode=kw.get("mode", "f32"))

    def reference_rows(self, sample) -> list[tuple]:
        """For each sampled request, the reference's logits at the served
        positions: one pass of the reference a request, kept while the same
        sample is judged again (the control and the faults of a
        calibration)."""
        key = tuple(req["rid"] for req, _ in sample)
        if getattr(self, "_rows", (None,))[0] == key:
            return self._rows[1]
        out = []
        for req, toks in sample:
            seq = self._sequence(req, toks)
            out.append((seq, self._served_rows(seq, len(req["prompt"]) - 1)))
        self._rows = (key, out)
        return out

    def gaps(self, sample, mode: str = "f32",
             fault: str | None = None) -> dict[str, float]:
        """As ``drivers/serve_hybrid_ssm.py``'s: the widest gap by which a
        served token's logit lies below the reference's best at its
        position; with a ``mode`` or a ``fault``, the token judged is the
        one that variant of the reference puts first there.  Every served
        position is compared (there are no experts, so no near-ties of a
        choice to set apart).  ``self.judged`` keeps every served
        position's gap for a calibration to read."""
        pad_new = int(self.cell["check"]["pad_new"])

        @jax.jit
        def below(logits, served):
            return jnp.max(logits, -1) - jnp.take_along_axis(
                logits, served[:, None], 1)[:, 0]

        gap = []
        for (req, toks), (seq, logits) in zip(
                sample, self.reference_rows(sample)):
            lp, new = len(req["prompt"]), len(toks)
            if mode != "f32" or fault is not None:
                bucket = min(self.max_len, max(
                    self.bucket_floor, 1 << (lp - 1).bit_length()))
                served = jnp.argmax(self._served_rows(
                    seq, lp - 1, mode=mode, fault=fault, prompt_len=lp,
                    pads=bucket - lp), axis=-1)
            else:
                served = jnp.zeros(pad_new, jnp.int32).at[:new].set(
                    jnp.asarray(toks, jnp.int32))
            gap.append(np.asarray(below(logits, served))[:new])
        gap = np.concatenate(gap)
        self.judged = {"gap": gap, "margin": np.full(gap.shape, np.inf)}
        return {"token_logit_gap": float(gap.max(initial=0.0)),
                "tokens_compared": int(gap.size)}
