"""Grouped-query decoder whose attention keeps the top keys of a learned
indexer (DeepSeek-style sparse attention), with softmax-routed experts: the
text decoder of Keye-VL-2.0 (the Qwen3-MoE block with an indexer in every
layer).

    layer i:  x = x + W_o Attn_i(RMS(x));   x = x + MoE_i(RMS'(x))
    logits = RMS_final(x) W_head                      (untied head)

Attention, for token ``t`` with ``h = RMS(x)``:

    q_{t,i}  = rope(RMS_q(W_q h_t)_i)           Hq query heads of d
    k_{s,g}  = rope(RMS_k(W_k h_s)_g)           Hk key heads; v_{s,g} = (W_v h_s)_g
    qI_{t,j} = rope_r((W_IQ h_t)_j)             Hi indexer heads of Di
    kI_s     = rope_r(LN(W_IK h_s))             one indexer key head of Di
    w_{t,j}  = (W_w h_t)_j / sqrt(Hi)
    I_{t,s}  = sum_j w_{t,j} relu(qI_{t,j} . kI_s) / sqrt(Di)
    S_t      = the index_topk positions s <= t of largest I_{t,s}
    o_{t,i}  = sum_{s in S_t} softmax_s(q_{t,i} . k_{s,g(i)} / sqrt(d)) v_{s,g(i)}

``rope`` rotates halves over the whole head at ``rope_theta``; ``rope_r``
does the same over the first ``index_rope_dim`` dims of an indexer head and
leaves the rest; ``RMS_q`` / ``RMS_k`` are one RMSNorm a head (a gain of
``d``); ``LN`` is a LayerNorm with gain and bias.  ``MoE`` is
``models/moe.DroplessMoE`` with ``scoring="softmax"``: softmax over every
expert, the top ``experts_per_token``, weights over their sum
(``norm_topk``), SwiGLU experts, no shared expert.

Precision: weights and matrix products in ``dtype``; the router, softmax,
norms and index scores in float32, the scores accumulated in float32 from
``qI`` and ``kI`` in ``dtype``; ``w`` is a float32 product and carries the
scores' constant factors.

The attention is ``ops/sparse_attention``: the kernel ``dsa_prefill`` over a
block from position 0 (training-mode forward, prefill), and in the one-token
STEP (``step_attention``) the kernel ``dsa_step_index`` (the new index key
written, each slot's index rows scored to its own position), the exact
selection, a gather of the selected K/V rows and the kernel
``dsa_step_attend`` over them.  Off the TPU their plain forms run.

Slot-decode mode keeps three ROWS leaves a layer (serving/kv_cache.py's
contract: validity is length-driven): ``cached_key`` and ``cached_value``
``(slots, max_len, Hk * d)``, and the indexer keys ``index_key`` ``(slots,
Di, max_len)``, positions along the last axis (the order the step's kernel
reads them in, unpadded on the chip).  A call with ``prompt_len`` is a
PREFILL from position 0: it writes the bucket's rows ``0 .. L - 1`` of all
three (the pads' rows lie past the prompt, invisible) and returns the
logits of the last real position; a call without it is the STEP, one token
a slot at the slot's position, which writes that position's three rows
first and so selects among ``0 .. pos``.  A slot that is not ``active``
writes no row (a free slot's rows stay as its last occupant left them) and
sends its stale token to no expert.

Beside the rows, each layer keeps per-slot STATE (the slot contract's
``slot_state``) that nothing in the model reads: what its attention took
and gave at one query, so that a comparison can hold the served path
itself to its rule.  Entry 0
of ``seen_q``, ``seen_o`` (the query and the attention's output, ``(slots,
2, Hq * d)``), ``seen_qi`` (the index queries, ``(slots, 2, Hi * Di)``) and
``seen_w`` (the head weights, float32, ``(slots, 2, Hi)``) is the slot's
last step, entry 1 its prefill's last prompt position; ``seen_rows``
``(slots, top_k)`` holds the rows the last step selected (-1 after a
prefill).  With the slot's rows, the query's selection and its attention
can be recomputed from what the program itself computed.

Departures from the published model: no vision tower (text tokens only; the
three mrope sections carry one position for a text token, which is this
rope); the indexer keys are held in ``dtype`` (bfloat16), where the
published cache holds them in FP8 after a Hadamard rotation (the rotation
leaves ``qI . kI`` unchanged)."""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
from jax import lax

from distributed_tensorflow_tpu.models.gpt import apply_rope, write_slot_rows
from distributed_tensorflow_tpu.models.mla_moe import RMSNorm
from distributed_tensorflow_tpu.models.moe import DroplessMoE
from distributed_tensorflow_tpu.models.slot_contract import SlotContract
from distributed_tensorflow_tpu.ops import sparse_attention as dsa

# tokens the expert layer takes at a time past its router (DroplessMoE's
# ``token_block``): what keeps a 65,536 bucket's gathered rows small
MOE_TOKEN_BLOCK = 4096
# what a slot's attention took and gave at one query (module docstring)
SEEN = ("seen_q", "seen_o", "seen_qi", "seen_w")


def step_attention(q, qi, w, keys, values, index_keys, new_key, at, put, *,
                   top_k: int, scale: float):
    """The one-token step's attention of every slot: the new index key
    written at ``put`` and each slot's index rows scored to ``at``
    (``dsa_step_index``), the selection (``step_select``), the selected K/V
    rows gathered and attended (``dsa_step_attend``).  ``q`` (slots, Hq,
    d), ``qi`` (slots, Hi, Di), ``w`` (slots, Hi), ``keys`` / ``values``
    (slots, max_len, Hk * d), ``index_keys`` (slots, Di, max_len),
    ``new_key`` (slots, Di), ``at`` / ``put`` (slots,) -> the output
    (slots, Hq, d), the selected rows (slots, K) and the index rows."""
    scores, index_keys = dsa.dsa_step_index(qi, w, index_keys, new_key, at,
                                            put)
    rows, count = dsa.step_select(scores, at, top_k)
    picked = [jnp.take_along_axis(t, rows[..., None], axis=1).astype(q.dtype)
              for t in (keys, values)]
    return (dsa.dsa_step_attend(q, *picked, count, scale=scale), rows,
            index_keys)


def rope_part(x, pos, dims: int, theta: float):
    """``apply_rope`` over the first ``dims`` of the last axis of ``x``
    (B, L, H, D); the rest as it is."""
    if dims == x.shape[-1]:
        return apply_rope(x, pos, theta)
    return jnp.concatenate([apply_rope(x[..., :dims], pos, theta),
                            x[..., dims:]], axis=-1)


class SparseIndexAttention(nn.Module):
    """The attention of one layer (module docstring)."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    index_heads: int
    index_head_dim: int
    index_rope_dim: int
    index_topk: int
    rope_theta: float
    eps: float
    max_len: int
    decode_slots: bool
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, pos, prompt_len, active=None):
        b, t, _ = x.shape
        hq, hk, d = self.heads, self.kv_heads, self.head_dim
        hi, di = self.index_heads, self.index_head_dim
        f32 = jnp.float32
        init = nn.initializers.lecun_normal()

        def matrix(name, shape):
            return self.param(name, init, shape, self.param_dtype)

        def vector(name, size, make=nn.initializers.ones_init()):
            return self.param(name, make, (size,), self.param_dtype).astype(f32)

        def product(v, w):      # ``dtype`` operands and result, as nn.Dense
            return jnp.dot(v.astype(self.dtype), w.astype(self.dtype))

        def rms(v, g):
            v = v.astype(f32)
            return (v * lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                  + self.eps) * g).astype(self.dtype)

        w_q, w_k = matrix("q_proj", (self.hidden, hq * d)), \
            matrix("k_proj", (self.hidden, hk * d))
        w_v, w_o = matrix("v_proj", (self.hidden, hk * d)), \
            matrix("o_proj", (hq * d, self.hidden))
        w_iq, w_ik = matrix("index_q", (self.hidden, hi * di)), \
            matrix("index_k", (self.hidden, di))
        w_iw = matrix("index_weights", (self.hidden, hi))
        q_norm, k_norm = vector("q_norm", d), vector("k_norm", d)
        ik_gain = vector("index_k_norm", di)
        ik_bias = vector("index_k_bias", di, nn.initializers.zeros_init())

        def query(rows, at):
            return apply_rope(rms(product(rows, w_q).reshape(
                rows.shape[:2] + (hq, d)), q_norm), at, self.rope_theta)

        q = query(x, pos)
        k = apply_rope(rms(product(x, w_k).reshape(b, t, hk, d), k_norm),
                       pos, self.rope_theta)
        v = product(x, w_v).reshape(b, t, hk, d)
        qi = rope_part(product(x, w_iq).reshape(b, t, hi, di), pos,
                       self.index_rope_dim, self.rope_theta)
        ki = product(x, w_ik).astype(f32)
        ki = ki - jnp.mean(ki, -1, keepdims=True)
        ki = (ki * lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True) + self.eps)
              * ik_gain + ik_bias).astype(self.dtype)
        ki = rope_part(ki[:, :, None], pos, self.index_rope_dim,
                       self.rope_theta)[:, :, 0]
        w = jnp.dot(x.astype(self.dtype), w_iw.astype(self.dtype),
                    preferred_element_type=f32) * (hi ** -0.5 * di ** -0.5)
        scale = d ** -0.5

        def out(o):
            return product(o.reshape(b, t, hq * d), w_o)

        def attend():
            """The block attends within itself from position 0."""
            return dsa.dsa_prefill(q, k, v, qi, w, ki, top_k=self.index_topk,
                                   scale=scale)

        if not self.decode_slots:
            return out(attend())
        # has_variable is False exactly during .init(): create the table,
        # write nothing (models/gpt.py's guard)
        ready = self.has_variable("cache", "cached_key")
        ck, cv = (self.variable("cache", name, jnp.zeros,
                                (b, self.max_len, hk * d), self.dtype)
                  for name in ("cached_key", "cached_value"))
        cki = self.variable("cache", "index_key", jnp.zeros,
                            (b, di, self.max_len), self.dtype)
        seen = [self.variable("cache", name, jnp.zeros, (b, 2, width), kind)
                for name, width, kind in zip(
                    SEEN, (hq * d, hq * d, hi * di, hi),
                    (self.dtype,) * 3 + (f32,))]
        seen_rows = self.variable("cache", "seen_rows", jnp.zeros,
                                  (b, min(self.index_topk, self.max_len)),
                                  jnp.int32)
        if not ready:
            return out(attend())
        if prompt_len is not None:
            # the bucket's rows from position 0; pad rows lie past the
            # prompt, invisible under the length
            for var, new in ((ck, k.reshape(b, t, hk * d)),
                             (cv, v.reshape(b, t, hk * d)),
                             (cki, jnp.swapaxes(ki, 1, 2))):
                var.value = lax.dynamic_update_slice(
                    var.value, new.astype(var.value.dtype), (0, 0, 0))
            o = attend()
            last = (prompt_len - 1).astype(jnp.int32)[:, None]
            at_last = lambda a: jnp.take_along_axis(
                a.reshape(b, t, -1), last[..., None], axis=1)
            # the last position's query made again from its row: a gather
            # of the bucket's queries would hold all of them past the kernel
            q_last = query(at_last(x), jnp.take_along_axis(pos, last, 1))
            for var, row in zip(seen, (q_last, o, qi, w)):
                row = row if row is q_last else at_last(row)
                var.value = jnp.broadcast_to(
                    row.reshape(b, 1, -1), var.value.shape).astype(
                        var.value.dtype)
            seen_rows.value = jnp.full_like(seen_rows.value, -1)
            return out(o)
        if t != 1:
            raise ValueError(
                "the sparse-attention step takes one token a slot: a token "
                "block has no selection of its own")
        at = pos[:, 0]
        # a slot that sits the round out writes nothing: its position is
        # taken past the table, which the writes drop
        live = jnp.ones((b,), bool) if active is None else active
        put = jnp.where(live, at, self.max_len)
        ck.value = write_slot_rows(ck.value, k.reshape(b, 1, hk * d),
                                   put[:, None])
        cv.value = write_slot_rows(cv.value, v.reshape(b, 1, hk * d),
                                   put[:, None])
        o, rows, cki.value = step_attention(
            q[:, 0], qi[:, 0], w[:, 0], ck.value, cv.value, cki.value,
            ki[:, 0], at, put, top_k=self.index_topk, scale=scale)
        for var, x in zip(seen, (q, o, qi, w)):
            var.value = var.value.at[:, 0].set(jnp.where(
                live[:, None], x.reshape(b, -1).astype(var.value.dtype),
                var.value[:, 0]))
        seen_rows.value = jnp.where(live[:, None], rows, seen_rows.value)
        return out(o[:, None])


class KeyeLM(nn.Module):
    """Decoder-only LM of the blocks above: token ids (B, L) -> next-token
    logits (B, L, V) in float32.

    ``param_dtype`` is what the weights are held in, ``dtype`` what the
    matrix products run in."""

    vocab_size: int = 512
    hidden: int = 64
    layers: int = 2
    heads: int = 4               # query heads
    kv_heads: int = 2
    head_dim: int = 16
    index_heads: int = 4
    index_head_dim: int = 16
    index_rope_dim: int = 8      # the rotated dims of an indexer head
    index_topk: int = 8
    num_experts: int = 8         # router width
    experts_per_token: int = 2
    expert_ffn: int = 32         # width of one routed expert
    rope_theta: float = 1e7
    eps: float = 1e-6
    max_len: int = 512
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    decode_slots: bool = False   # serving: the batch dim is a SLOT table
                                 # (serving/kv_cache.py), positions are the
                                 # caller's

    causal_lm = True

    def _topk(self) -> int:
        return min(self.index_topk, self.max_len)

    def slot_index_rows(self, lengths, advancing) -> dict[str, int]:
        """What a step over the ``advancing`` slots at ``lengths`` reads,
        summed over the slots and the layers: the index rows it scores (a
        slot's query at position ``length`` scores ``length + 1`` rows) and
        the K/V rows it attends (at most ``index_topk`` of them)."""
        rows = np.asarray(lengths, np.int64)[np.asarray(advancing)] + 1
        return {"index_rows": int(rows.sum()) * self.layers,
                "attended_rows": int(np.minimum(rows, self._topk()).sum())
                * self.layers}

    def prefill_index_pairs(self, prompt_len: int) -> dict[str, int]:
        """The (query, key) pairs a prompt's tokens score and attend, summed
        over the layers: ``s <= t`` for every ``t`` of the prompt, and at
        most ``index_topk`` of them a query."""
        n, k = int(prompt_len), self._topk()
        near = min(n, k)
        attended = near * (near + 1) // 2 + (n - near) * k
        return {"index_pairs": n * (n + 1) // 2 * self.layers,
                "attended_pairs": attended * self.layers}

    def slot_decode_clone(self, *, mesh=None,
                          kv_quant: bool = False) -> "KeyeLM":
        """The module ``SlotKVCache`` serves from: the same under any
        ``mesh`` (replicated over a 'model' axis), and with float rows
        (its contract declares no int8 rows, so the table refuses
        ``kv_quant``)."""
        return self.clone(decode=True, decode_slots=True)

    def slot_contract(self) -> SlotContract:
        """Per-slot state, none narrowed by the table's dtype: what each
        layer's attention took and gave at one query (the step has no
        token block to resume); a round reports the index rows it scored
        and the rows it attended, a prefill the pairs."""
        return SlotContract(
            takes_active=True,
            slot_state={name: False for name in SEEN + ("seen_rows",)},
            expert_layers=self.layers, num_experts=self.num_experts,
            experts_per_token=self.experts_per_token,
            round_attrs=self.slot_index_rows,
            prefill_attrs=self.prefill_index_pairs)

    @nn.compact
    def __call__(self, token_ids, train: bool = False, positions=None,
                 prompt_len=None, active=None):
        b, t = token_ids.shape
        if self.decode != self.decode_slots:
            raise ValueError(
                "the only decode mode is the slot table: set decode and "
                "decode_slots together (SlotKVCache does)")
        if (positions is not None) != self.decode_slots:
            raise ValueError("positions are given in decode_slots mode, "
                             "and only there")
        if prompt_len is not None and not self.decode_slots:
            raise ValueError("prompt_len marks a slot prefill")
        if t > self.max_len:
            raise ValueError(
                f"sequence length {t} exceeds max_len={self.max_len}")
        pos = positions if positions is not None \
            else jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :],
                                  (b, t))
        # pad tokens of a prefill bucket, and the stale tokens of slots
        # that sit a round out (``active``, the step's), go to no expert
        if prompt_len is not None:
            routed = jnp.arange(t)[None, :] < prompt_len[:, None]
        else:
            routed = None if active is None \
                else jnp.broadcast_to(active[:, None], (b, t))

        kinds = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        x = nn.Embed(self.vocab_size, self.hidden, **kinds,
                     name="token_embed")(token_ids)
        for i in range(self.layers):
            x = x + SparseIndexAttention(
                self.hidden, self.heads, self.kv_heads, self.head_dim,
                self.index_heads, self.index_head_dim, self.index_rope_dim,
                self.index_topk, self.rope_theta, self.eps, self.max_len,
                self.decode_slots, **kinds, name=f"attn_{i}")(
                    RMSNorm(self.eps, **kinds, name=f"norm_{i}")(x), pos,
                    prompt_len, active)
            n = RMSNorm(self.eps, **kinds, name=f"ffn_norm_{i}")(x)
            x = x + DroplessMoE(
                num_experts=self.num_experts, top_k=self.experts_per_token,
                hidden=self.expert_ffn, scoring="softmax",
                token_block=MOE_TOKEN_BLOCK,
                **kinds, name=f"ffn_{i}")(
                    n.reshape(b * t, self.hidden),
                    None if routed is None else routed.reshape(b * t)
            ).reshape(b, t, self.hidden)
        if prompt_len is not None:  # the one position whose logits sample
            x = jnp.take_along_axis(
                x, (prompt_len - 1)[:, None, None].astype(jnp.int32), axis=1)
        x = RMSNorm(self.eps, **kinds, name="final_norm")(x)
        return nn.Dense(self.vocab_size, use_bias=False, **kinds,
                        name="lm_head")(x).astype(jnp.float32)
