"""``drivers/serve.py``'s window for the sparse-attention / softmax-expert
decoder: the same ``ContinuousBatcher.run`` over a ``SlotKVCache``, the same
traffic generator and limits; what differs is the model, that a slot keeps
the indexer's keys beside its K/V rows, and that prompts run to tens of
thousands of tokens, past the indexer's ``top_k``.

* The model is built BEFORE the weights: a program that does not know
  ``keye`` fails at once, not after 6 GB were made.
* Weights: ``lib/keye_weights.py``, bfloat16, made once and kept for the
  comparison: the program's tree is these very arrays re-labelled
  (``drivers/keye_tree.py``), so 6.25 GB are held once.
* ``max_len`` is the cell's (``job.max_len``), not the model's 262,144
  positions.
* ``model_flops`` come from ``lib/keye_costs.py``: a token passes its
  ``num_experts_per_tok`` experts, scores every earlier key and attends its
  selection.
* The sample always holds the longest finished request, a request that was
  admitted into a slot whose last occupant of the window was longer than it
  (the ``prefill`` span's ``slot``; of those the one with the shortest
  prompt) and the PROBE: the request that holds, when the window closes, the
  slot with the most stale rows past its length (the last occupant of each
  slot is still there).  Before the table is freed the probe's rows of
  every layer are copied out, with what each layer's attention took and
  gave at two of its queries, as the served programs themselves left it in
  the table (``models/keye.py``'s ``seen_*`` state): the last step, at the
  probe's last fed position, and its prefill's last prompt position.
* The comparison runs ``lib/keye_reference.py`` over each sampled request
  once, layer by layer, padded to a multiple of ``check.pad_to``, and reads
  three numbers:

  - ``token_logit_gap``: the widest gap by which a served token's logit
    lies below the reference's best at its position (``check.pad_new`` rows
    from the prompt's last position on);
  - ``attend_gap``: at the probe's two queries, in every layer, the
    program's attention output against attention computed in float32 from
    the program's OWN query, K and V rows over the rows the program's own
    index query selects among the slot's index rows (exactly, as
    ``lax.top_k``), ``|o - o'| / |o'|``, the largest;
  - ``selection_gap``: at the probe's last ``check.selection_queries``
    fed positions, in every layer, the rows that the reference's index
    query (in the table's dtype, as the program's kernels take it) selects
    among the index rows the window wrote into the probe's slot, against
    the reference's own selection: the size of the difference of the two
    sets over twice ``top_k`` (0 the same rows, 1 none in common), the
    mean.  It holds the keys the program wrote to the reference's; the
    program's own queries would add their bfloat16 rounding to both sides
    of every near-equal pair and bury the float8 control (section 2 of
    PERF.md).

  ``gaps(sample, mode=..., fault=...)`` gives what the float8 control and a
  fault planted in the reference read; ``planted(fault)`` plants one of
  ``PROGRAM_FAULTS`` in the program itself, for a calibration to serve
  through."""

from __future__ import annotations

import contextlib
import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.drivers import keye_tree, serve
from benchmarks.lib import (keye_costs, keye_reference, keye_weights,
                            program_spans)

PROGRAM_FAULTS = (
    "prefill_dense",        # the prefill attends every key s <= t
    "prefill_last_rows",    # the prefill selects the last top_k rows
    "step_dense",           # the step attends every row of the slot
    "step_last_rows",       # the step selects the last top_k rows
    "stale_rows",           # the step scores and selects the whole slot
)
SEEN = ("seen_q", "seen_o", "seen_qi", "seen_w", "seen_rows")


@contextlib.contextmanager
def planted(fault: str):
    """``fault`` (``PROGRAM_FAULTS``) planted in ``ops/sparse_attention``
    or ``models/keye`` while the block runs: a program traced inside it
    keeps the fault, on the kernels' path and on their plain forms."""
    from distributed_tensorflow_tpu.models import keye
    from distributed_tensorflow_tpu.ops import sparse_attention as dsa

    if fault not in PROGRAM_FAULTS:
        raise ValueError(f"unknown program fault {fault!r}")
    prefill, index, select, step = (dsa.dsa_prefill, dsa.dsa_step_index,
                                    dsa.step_select, keye.step_attention)

    def prefill_last_rows(q, k, v, qi, w, ki, **kw):
        # one index dim of one head carries the position: the scores rise
        # with it, so the top_k are the last rows
        at = jnp.broadcast_to(jnp.arange(ki.shape[1], dtype=jnp.float32),
                              ki.shape[:2])
        return prefill(q, k, v,
                       jnp.zeros(qi.shape, jnp.float32).at[..., 0, 0].set(1),
                       jnp.zeros(w.shape, jnp.float32).at[..., 0].set(1),
                       jnp.zeros(ki.shape, jnp.float32).at[..., 0].set(at),
                       **kw)

    def step_dense(q, qi, w, keys, values, index_keys, new_key, at, put, *,
                   top_k, scale):
        _, rows, index_keys = step(q, qi, w, keys, values, index_keys,
                                   new_key, at, put, top_k=top_k, scale=scale)
        return (dsa.step_attend_plain(q, keys.astype(q.dtype),
                                      values.astype(q.dtype), at + 1,
                                      scale=scale), rows, index_keys)

    def step_last_rows(scores, pos, top_k):
        k = min(top_k, scores.shape[1])
        rows = jnp.maximum(pos[:, None] - k + 1, 0) + jnp.arange(k)[None]
        return rows, jnp.minimum(pos + 1, k)

    def whole(pos, size):
        return jnp.full_like(pos, size - 1)

    patches = {
        "prefill_dense": [(dsa, "dsa_prefill", lambda *a, top_k, **kw:
                           prefill(*a, top_k=1 << 30, **kw))],
        "prefill_last_rows": [(dsa, "dsa_prefill", prefill_last_rows)],
        "step_dense": [(keye, "step_attention", step_dense)],
        "step_last_rows": [(dsa, "step_select", step_last_rows)],
        "stale_rows": [
            (dsa, "dsa_step_index",
             lambda qi, w, keys, new, pos, put, **kw: index(
                 qi, w, keys, new, whole(pos, keys.shape[2]), put, **kw)),
            (dsa, "step_select", lambda scores, pos, top_k: select(
                scores, whole(pos, scores.shape[1]), top_k))],
    }[fault]
    saved = [(module, name, getattr(module, name))
             for module, name, _ in patches]
    try:
        for module, name, fn in patches:
            setattr(module, name, fn)
        yield
    finally:
        for module, name, fn in saved:
            setattr(module, name, fn)


@jax.jit
def below(w, rows, served):
    """How far each served token's logit lies below the reference's best,
    from the reference's hidden rows (the logits are made here)."""
    logits = keye_reference.head_fn(w, rows)
    return jnp.max(logits, -1) - jnp.take_along_axis(
        logits, served[:, None], 1)[:, 0]


@functools.partial(jax.jit, static_argnames="mode")
def first_choice(w, rows, mode):
    """The token a variant of the reference puts first at each row."""
    return jnp.argmax(keye_reference.head_fn(w, rows, mode=mode), -1)


@functools.partial(jax.jit, static_argnames=("top_k", "kv_heads"))
def witness(seen, keys, values, index, at, *, top_k: int, kv_heads: int):
    """One layer of the probe: ``seen`` its ``seen_*`` state (two entries,
    the queries at positions ``at``), ``keys`` / ``values`` (n, Hk * d) and
    ``index`` (Di, n) its rows.  -> the selection each entry's index query
    makes over the rows (2, n) bool, exactly, and how far the program's
    output lies from attention over that selection with the program's
    query: ``|o - o'| / |o'|`` (2,)."""
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    n = keys.shape[0]
    qi = seen["seen_qi"].astype(f32).reshape(2, -1, index.shape[0])
    r = jnp.einsum("ejd,dn->ejn", qi, index.astype(f32), precision=hi)
    scores = jnp.einsum("ejn,ej->en", jax.nn.relu(r),
                        seen["seen_w"].astype(f32), precision=hi)
    mask = keye_reference.select(scores, at, top_k)
    d = keys.shape[1] // kv_heads
    q = seen["seen_q"].astype(f32).reshape(2, kv_heads, -1, d)
    s = jnp.einsum("ehgd,nhd->ehgn", q,
                   keys.astype(f32).reshape(n, kv_heads, d),
                   precision=hi) * d ** -0.5
    p = jax.nn.softmax(jnp.where(mask[:, None, None], s, -jnp.inf), -1)
    o = jnp.einsum("ehgn,nhd->ehgd", p,
                   values.astype(f32).reshape(n, kv_heads, d),
                   precision=hi).reshape(2, -1)
    gap = jnp.linalg.norm(seen["seen_o"].astype(f32) - o, axis=-1) \
        / jnp.linalg.norm(o, axis=-1)
    return mask, gap


class Run(serve.Run):
    def __init__(self, cell: dict, config: dict, *, seed: int, seconds: float,
                 devices, note=print):
        self.cell, self.config, self.job = cell, config, cell["job"]
        self.mix = cell["traffic"]
        self.seed, self.seconds = int(seed), float(seconds)
        self.devices, self.note = list(devices), note
        self.vocab = int(config["vocab_size"])
        self.max_len = int(self.job["max_len"])
        self.dims = keye_reference.dims_of(config)
        self.weights = None
        self.probe = None

    # ------------------------------------------------------------ set-up
    def model(self):
        from distributed_tensorflow_tpu.models import create_model

        return create_model(
            "keye", dtype=self.job["dtype"],
            param_dtype=self.job["param_dtype"],
            **keye_tree.model_kwargs(self.config, self.max_len))

    def build(self) -> None:
        model = self.model()
        self.weights = keye_weights.make(self.config, self.seed)
        self.table(model)

    def table(self, model) -> None:
        """The slot table and its batcher over the weights already made."""
        from distributed_tensorflow_tpu.parallel import mesh as meshlib
        from distributed_tensorflow_tpu.serving.kv_cache import SlotKVCache
        from distributed_tensorflow_tpu.serving.scheduler import (
            ContinuousBatcher)

        mesh = None if len(self.devices) == 1 else meshlib.create_mesh(
            devices=self.devices)
        self.kv = SlotKVCache(model, keye_tree.to_flax(self.weights),
                              int(self.job["slots"]), mesh=mesh, greedy=True,
                              kv_dtype=jnp.dtype(self.job["kv_dtype"]))
        self.batcher = ContinuousBatcher(self.kv)
        self.bucket_floor = int(self.kv.prefill_bucket)

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
        # which slot each request was admitted into, in order of admission
        self.slots_held = [
            (r["rid"], r["attrs"]["slot"]) for r in program_spans.named(
                program_spans.window({"root": "serve_run"}), "prefill")
            if r["attrs"].get("slot") is not None]
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
            "model_flops": sum(keye_costs.serve_flops(
                self.config, r.prompt_len, len(r.tokens)) for r in served),
            "decode_context_mean": behind / steps if steps else None,
        }

    def _total(self) -> dict[int, int]:
        return {req["rid"]: len(req["prompt"]) + len(toks)
                for req, toks in self.finished}

    def _take_probe(self) -> dict | None:
        """The last occupant of each slot is still in it: of those, the one
        with the most rows of an earlier, longer occupant past its own
        length (the longest where none has any); its slot's rows of every
        layer to its length and the ``seen_*`` state, copied to the host,
        and the positions of the two queries that state is of."""
        total, before, last = self._total(), {}, {}
        for rid, slot in self.slots_held:
            if slot in last:
                before[slot] = max(before.get(slot, 0),
                                   total.get(last[slot], 0))
            last[slot] = rid
        held = [(before.get(slot, 0) - total[rid], total[rid], slot, rid)
                for slot, rid in last.items() if rid in total]
        if not held:
            return None
        stale, n, slot, rid = max(held)
        n -= 1                      # the positions fed: all but the last
        prompt = next(len(r["prompt"]) for r, _ in self.finished
                      if r["rid"] == rid)
        layers = []
        for i in range(int(self.config["num_hidden_layers"])):
            c = self.kv.cache[f"attn_{i}"]
            layers.append({
                "keys": np.asarray(c["cached_key"][slot, :n]),
                "values": np.asarray(c["cached_value"][slot, :n]),
                "index": np.asarray(c["index_key"][slot, :, :n]),
                **{name: np.asarray(c[name][slot]) for name in SEEN}})
        return {"rid": rid, "slot": slot, "stale": max(stale, 0),
                "at": np.array([n - 1, prompt - 1], np.int32),
                "layers": layers}

    # ------------------------------------------------------------- check
    def free(self) -> None:
        """The table and its programs refer to each other: only a
        collection gives the table and the programs' temporaries back to
        the reference."""
        super().free()
        gc.collect()

    def check(self, obs: dict) -> list[dict]:
        if not self.finished:       # nothing to judge: every limit is missed
            return [{"name": name, "value": 1e9, "limit": limit}
                    for name, limit in self.cell["limits"].items()]
        sample = self.sample()
        self.free()
        readings = self.gaps(sample)
        self.note(f"reference read {readings['tokens_compared']} served "
                  f"tokens of {len(sample)} requests; selection_gap "
                  f"{readings['selection_gap']:.4f}, attend_gap "
                  f"{readings['attend_gap']:.4g}")
        readings["requests_failed"] = obs["failed"]
        return [{"name": name, "value": readings[name], "limit": limit}
                for name, limit in self.cell["limits"].items()]

    def sample(self) -> list[tuple[dict, list[int]]]:
        """``drivers/serve.py``'s sample (drawn from the seed, the longest
        among them), with one of the requests that took the slot of a
        longer occupant and the probe always in it.  Called while the slot
        table is still there: the probe's rows are taken here."""
        n = int(self.cell["check"]["sample_requests"])
        self.probe = self._take_probe()     # while the table is there
        rng = np.random.default_rng([self.seed, 4])
        order = [int(i) for i in rng.permutation(len(self.finished))]
        total = self._total()
        rid_of = lambda i: self.finished[i][0]["rid"]
        longest = max(range(len(self.finished)),
                      key=lambda i: total[rid_of(i)])
        last, after_longer = {}, set()
        for rid, slot in self.slots_held:
            if total.get(last.get(slot), 0) > total.get(rid, 1 << 62):
                after_longer.add(rid)
            last[slot] = rid
        picked = [longest] + sorted(
            (i for i in order if rid_of(i) in after_longer and i != longest),
            key=lambda i: len(self.finished[i][0]["prompt"]))[:1]
        if self.probe is not None:
            picked += [i for i in order if rid_of(i) == self.probe["rid"]
                       and i not in picked]
        picked += [i for i in order if i not in picked][:max(n - len(picked),
                                                             0)]
        self.note(f"sample of {len(picked)}: "
                  f"{[total[rid_of(i)] for i in picked]} tokens, probe "
                  f"{None if self.probe is None else self.probe['rid']} "
                  f"({0 if self.probe is None else self.probe['stale']} "
                  f"stale rows)")
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

    def _queries(self) -> np.ndarray:
        """The probe's last fed positions, where the selection is read."""
        n = self.probe["layers"][0]["keys"].shape[0]
        q = int(self.cell["check"]["selection_queries"])
        return np.arange(max(0, n - q), n, dtype=np.int32)

    def _pass(self, req, toks, mode="f32", fault=None):
        """One reference pass over a request: the hidden rows of the served
        positions (the logits are made from them where they are read: a
        request's 2,048 rows of 151,936 logits are 1.2 GB) and their least
        choice margin, and for the probe each layer's ``(qI, w, kI)`` at its
        selection's queries."""
        pad_new = int(self.cell["check"]["pad_new"])
        seq = self._sequence(req, toks)
        t0 = time.perf_counter()
        probe = self.probe is not None and req["rid"] == self.probe["rid"]
        keep = jnp.asarray(self._queries()) if probe else None
        out = keye_reference.hidden_fn(
            self.weights, seq, self.dims, mode=mode, fault=fault,
            margins=True, keep=keep)
        hidden, margin = out[:2]
        first = len(req["prompt"]) - 1
        rows = jax.lax.dynamic_slice_in_dim(hidden, first, pad_new, 0)
        margin = np.asarray(margin)[first:first + pad_new]
        self.note(f"reference pass ({mode}, {fault}) over {seq.shape[0]} "
                  f"positions: {time.perf_counter() - t0:.1f} s")
        return rows, margin, out[2] if probe else None

    def reference_rows(self, sample) -> list[tuple]:
        """For each sampled request, the reference's pass (``_pass``): kept
        while the same sample is judged again (the control and the faults
        of a calibration)."""
        key = tuple((req["rid"], tuple(toks)) for req, toks in sample)
        if getattr(self, "_rows", (None,))[0] == key:
            return self._rows[1]
        out = [self._pass(req, toks) for req, toks in sample]
        self._rows = (key, out)
        return out

    def _selections(self, kept, mode="f32", rows=False) -> list:
        """Each layer's selection (queries, n) at the probe's selection
        queries, made from a reference pass's ``(qI, w, kI)`` (of its
        ``mode``); with ``rows``, the reference's ``qI`` in the table's
        dtype over the index rows the window wrote into the probe's slot."""
        at = jnp.asarray(self._queries())
        n = self.probe["layers"][0]["keys"].shape[0]
        out = []
        for (qi, wt, ki), layer in zip(kept, self.probe["layers"]):
            if rows:
                index = jnp.asarray(layer["index"])
                qi, ki = qi.astype(index.dtype), index.T
            out.append(keye_reference.select(keye_reference.index_scores(
                qi, wt, ki[:n], self.dims, mode), at, self.dims["top_k"]))
        return out

    def _witness(self) -> float:
        """``attend_gap`` (module docstring), over the layers and the two
        queries the probe's ``seen_*`` state is of."""
        worst = 0.0
        for layer in self.probe["layers"]:
            _, gap = witness({name: jnp.asarray(layer[name])
                              for name in SEEN[:4]},
                             jnp.asarray(layer["keys"]),
                             jnp.asarray(layer["values"]),
                             jnp.asarray(layer["index"]),
                             jnp.asarray(self.probe["at"]),
                             top_k=self.dims["top_k"],
                             kv_heads=self.dims["kv_heads"])
            worst = max(worst, float(jnp.max(gap)))
        return worst

    def gaps(self, sample, mode: str = "f32",
             fault: str | None = None) -> dict[str, float]:
        """As ``drivers/serve_mla_moe.py``'s: the widest gap by which a
        served token's logit lies below the reference's best at its
        position; with a ``mode`` or a reference ``fault`` the token judged
        is the one that variant puts first there, and the selection judged
        is that variant's.  Beside it ``selection_gap`` and ``attend_gap``
        at the probe (module docstring).  ``self.judged`` keeps every served
        position's gap and choice margin for a calibration to read."""
        variant = mode != "f32" or fault is not None
        attend = 1e9 if self.probe is None else self._witness()
        gap, margin, selection = [], [], 1e9
        for (req, toks), (rows, least, kept) in zip(
                sample, self.reference_rows(sample)):
            new = len(toks)
            if variant:
                vrows, _, vkept = self._pass(req, toks, mode, fault)
                served = first_choice(self.weights, vrows, mode)
            else:
                served = jnp.zeros(rows.shape[0], jnp.int32).at[:new].set(
                    jnp.asarray(toks, jnp.int32))
            gap.append(np.asarray(below(self.weights, rows, served))[:new])
            margin.append(least[:new])
            if kept is not None:
                truth = self._selections(kept)
                got = self._selections(vkept, mode) if variant \
                    else self._selections(kept, rows=True)
                selection = float(np.mean([
                    np.asarray(jnp.sum(a != b, axis=1))
                    / (2.0 * self.dims["top_k"])
                    for a, b in zip(got, truth)]))
        gap, margin = np.concatenate(gap), np.concatenate(margin)
        self.judged = {"gap": gap, "margin": margin}
        return {"token_logit_gap": float(gap.max(initial=0.0)),
                "selection_gap": selection, "attend_gap": attend,
                "tokens_compared": int(gap.size)}
