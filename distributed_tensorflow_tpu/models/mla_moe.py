"""Latent-attention, sparse-expert decoder (the DeepSeek-V3 block family).

What ``models/gpt.GPTLM`` is not: RMSNorm, no biases, RoPE on part of each
head, multi-head LATENT attention (MLA, no query compression), a gated
SiLU MLP in the leading dense layers and a dropless sigmoid-routed expert
layer with shared experts (``models/moe.DroplessMoE``) in every later
one, an untied head.

Block: ``x = x + Attn(RMS_1(x))``, ``x = x + F(RMS_2(x))``; final RMS, then
``h -> vocab``.  With ``H`` heads, ``d_n`` / ``d_r`` the un-rotated and
rotated parts of a query or key head, ``d_v`` the value head, ``r`` the
latent rank:

    q = x W_q                  (h -> H (d_n + d_r)), per head [q_n, q_r]
    [c_raw, k_r] = x W_kva     (h -> r + d_r);  c = RMS_kv(c_raw)
    [k_n, v] = c W_kvb         (r -> H (d_n + d_v)), per head

``k_r`` is ONE head shared by all query heads.  RoPE rotates ``q_r`` and
``k_r`` only, adjacent pairs ``(2i, 2i+1)`` in place (the published code
permutes to a half-split layout and rotates halves: the same scores).

Attention has two forms of one function (tests/test_mla_moe.py holds them
equal):

* EXPANDED (training-mode forward, prefill): ``k = [k_n, k_r]``, ``q =
  [q_n, q_r]``, scores ``q k^T / sqrt(d_n + d_r)``, causal, softmax in
  float32, ``o = P v``.  Computed in query blocks of ``ATTN_QUERY_BLOCK``
  against the keys at or before the block, so ``H x L x L`` scores never
  exist.  A block whose keys fit ``ATTN_KEY_BLOCK`` meets them in one
  piece (one softmax); a later one takes them a key block at a time and
  carries the softmax across the pieces in float32 (running maximum, sum
  and unnormalised output), so no score tile is wider than the key block
  either: on the v5e the softmax over rows of more than 4,096 keys takes
  36 times what its bytes need, whatever the query block (PERF.md
  section 5).
* ABSORBED (the slot-decode step): the cache holds, a token a layer, ``c``
  (``r`` values) and the rotated ``k_r`` (``d_r``) and nothing else.  With
  ``W_kvb`` split per head into ``W_k`` and ``W_v``: ``q_c = q_n W_k^T``,
  scores ``(q_c . c + q_r . k_r) / sqrt(d_n + d_r)``, ``o = (P c) W_v``.

Slot-decode mode (``decode=True, decode_slots=True``, what
``serving/kv_cache.SlotKVCache`` clones a model into) follows
``models/gpt``'s contracts: ``cache`` leaves ``(slots, max_len, ...)``,
rows written through ``write_slot_rows``, validity driven by the caller's
positions, ``T >= 1`` tokens a slot in one call.  A call with
``prompt_len`` is a PREFILL from position 0: the expanded form over the
whole padded block, its latents written into the table in one piece, and
logits at the last prompt position only.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.models.gpt import write_slot_rows
from distributed_tensorflow_tpu.models.moe import DroplessMoE, SwiGLU

# expanded form: H x 512 x 4096 float32 scores at most
ATTN_QUERY_BLOCK = 512
ATTN_KEY_BLOCK = 4096       # chosen on the v5e: PERF.md section 5


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * g`` in float32."""

    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.ones_init(), (x.shape[-1],),
                       self.param_dtype)
        x = x.astype(jnp.float32)
        y = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.eps)
        return (y * g.astype(jnp.float32)).astype(self.dtype)


def rope_adjacent(x, pos, theta: float):
    """Rotate the adjacent pairs ``(2i, 2i+1)`` of the last axis by
    ``pos * theta^(-2i/d)``.  ``x``: (B, L, ..., d); ``pos``: (B, L)."""
    d2 = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = pos.astype(jnp.float32)[..., None] * inv               # (B, L, d/2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (d2,))
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def causal_attention_blocked(q, k, v, scale: float,
                             block: int = ATTN_QUERY_BLOCK,
                             key_block: int = ATTN_KEY_BLOCK):
    """Causal softmax attention from position 0, in query blocks against
    key blocks.

    ``q``, ``k``: (B, L, H, d_qk); ``v``: (B, L, H, d_v).  Query block
    ``i`` attends to keys ``[0, (i + 1) * block)`` only, so the work is the
    causal half.  Where those keys fit one key block they are taken in one
    piece: one product, one softmax, one product.  Where they do not, they
    are taken ``key_block`` at a time and the softmax is carried across
    the pieces (running row maximum ``m``, row sum ``l`` and unnormalised
    output ``acc``, all float32), so no score tile wider than ``key_block``
    is ever live; only the piece on the diagonal is masked."""
    length = q.shape[1]

    def scores(lo, hi, k0, k1, masked):
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, k0:k1],
                       preferred_element_type=jnp.float32) * scale
        if not masked:
            return s
        mask = (jnp.arange(k0, k1)[None, :] <= jnp.arange(lo, hi)[:, None])
        return jnp.where(mask, s, -jnp.inf)

    outs = []
    for lo in range(0, length, block):
        hi = min(lo + block, length)
        if hi <= key_block:
            p = jax.nn.softmax(scores(lo, hi, 0, hi, True), axis=-1)
            outs.append(jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype),
                                   v[:, :hi]))
            continue
        # the first piece holds key 0, which every query sees: m is finite
        # from there on and a row with no key in a later piece adds zeros
        m = l = acc = None
        for k0 in range(0, hi, key_block):
            k1 = min(k0 + key_block, hi)
            s = scores(lo, hi, k0, k1, masked=k1 > lo + 1)
            # the result does not depend on m: no gradient through it
            top = lax.stop_gradient(jnp.max(s, axis=-1))
            m_new = top if m is None else jnp.maximum(m, top)
            p = jnp.exp(s - m_new[..., None])
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype),
                            v[:, k0:k1], preferred_element_type=jnp.float32)
            if m is None:
                l, acc = jnp.sum(p, axis=-1), pv
            else:
                a = jnp.exp(m - m_new)
                l = l * a + jnp.sum(p, axis=-1)
                acc = acc * a[..., None] + pv
            m = m_new
        outs.append(jnp.swapaxes(acc / l[..., None], 1, 2).astype(v.dtype))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


class LatentAttention(nn.Module):
    """MLA without query compression (module docstring: both forms)."""

    hidden: int
    heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    kv_rank: int
    rope_theta: float
    eps: float
    max_len: int
    decode_slots: bool
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, pos, prefill: bool):
        b, t, _ = x.shape
        hn, dn, dr, dv, r = (self.heads, self.qk_nope_dim, self.qk_rope_dim,
                             self.v_dim, self.kv_rank)
        scale = 1.0 / math.sqrt(dn + dr)

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        q = dense(hn * (dn + dr), "q_proj")(x).reshape(b, t, hn, dn + dr)
        q_n, q_r = q[..., :dn], rope_adjacent(q[..., dn:], pos,
                                              self.rope_theta)
        kva = dense(r + dr, "kv_a_proj")(x)
        c = RMSNorm(self.eps, self.dtype, self.param_dtype,
                    name="kv_a_norm")(kva[..., :r])               # (b, t, r)
        k_r = rope_adjacent(kva[..., r:], pos, self.rope_theta)   # (b, t, dr)
        # held as one array: the absorbed form reads it per head
        w_kvb = self.param("kv_b_proj", nn.initializers.lecun_normal(),
                           (r, hn * (dn + dv)),
                           self.param_dtype).astype(self.dtype)
        out = dense(self.hidden, "o_proj")

        def expanded():
            kv = jnp.dot(c, w_kvb).reshape(b, t, hn, dn + dv)
            k = jnp.concatenate(
                [kv[..., :dn],
                 jnp.broadcast_to(k_r[:, :, None, :], (b, t, hn, dr))], -1)
            o = causal_attention_blocked(
                jnp.concatenate([q_n, q_r], -1), k, kv[..., dn:], scale)
            return out(o.reshape(b, t, hn * dv))

        if not self.decode_slots:
            return expanded()
        # has_variable is False exactly during .init(): create the table,
        # write nothing (models/gpt.py's guard)
        ready = self.has_variable("cache", "cached_latent")
        cc = self.variable("cache", "cached_latent", jnp.zeros,
                           (b, self.max_len, r), self.dtype)
        cr = self.variable("cache", "cached_rope_key", jnp.zeros,
                           (b, self.max_len, dr), self.dtype)
        if not ready:
            return expanded()
        if prefill:
            # one piece from position 0; pad rows past the prompt hold
            # latents of pad tokens, invisible under the length mask
            cc.value = lax.dynamic_update_slice_in_dim(
                cc.value, c.astype(cc.value.dtype), 0, axis=1)
            cr.value = lax.dynamic_update_slice_in_dim(
                cr.value, k_r.astype(cr.value.dtype), 0, axis=1)
            return expanded()
        cc.value = write_slot_rows(cc.value, c.astype(cc.value.dtype), pos)
        cr.value = write_slot_rows(cr.value, k_r.astype(cr.value.dtype), pos)
        # ABSORBED: W_kvb never touches the table; scores and values are
        # taken against the latents themselves
        w = w_kvb.reshape(r, hn, dn + dv)
        table_c, table_r = (cc.value.astype(self.dtype),
                            cr.value.astype(self.dtype))
        q_c = jnp.einsum("bthd,rhd->bthr", q_n, w[..., :dn])
        s = (jnp.einsum("bthr,blr->bhtl", q_c, table_c,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bthe,ble->bhtl", q_r, table_r,
                          preferred_element_type=jnp.float32)) * scale
        valid = (jnp.arange(self.max_len)[None, None, :]
                 <= pos[:, :, None])                               # (b, t, l)
        p = jax.nn.softmax(jnp.where(valid[:, None], s, -jnp.inf), axis=-1)
        o_c = jnp.einsum("bhtl,blr->bthr", p.astype(self.dtype), table_c)
        o = jnp.einsum("bthr,rhd->bthd", o_c, w[..., dn:])
        return out(o.reshape(b, t, hn * dv))


class LatentMoEBlock(nn.Module):
    """Pre-norm block; ``moe`` (``DroplessMoE``'s fields) is None in a
    leading dense layer, whose ``F`` is the SwiGLU MLP."""

    attn: dict
    dense_ffn: int
    moe: dict | None
    eps: float
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, pos, prefill: bool, valid):
        norm = lambda name: RMSNorm(self.eps, self.dtype, self.param_dtype,
                                    name=name)
        x = x + LatentAttention(**self.attn, name="attn")(
            norm("attn_norm")(x), pos, prefill)
        y = norm("ffn_norm")(x)
        if self.moe is None:
            y = SwiGLU(self.dense_ffn, self.dtype, self.param_dtype,
                       name="mlp")(y)
        else:
            b, t, d = y.shape
            y = DroplessMoE(**self.moe, dtype=self.dtype,
                            param_dtype=self.param_dtype, name="moe")(
                y.reshape(b * t, d),
                None if valid is None else valid.reshape(b * t))
            y = y.reshape(b, t, d)
        return x + y


class LatentMoELM(nn.Module):
    """Decoder-only LM of the block above: token ids (B, L) -> next-token
    logits (B, L, V) in float32.

    ``param_dtype`` is what the weights are held in (bfloat16 for serving:
    the checkpoints of this family are published in it), ``dtype`` what
    the matrix products run in; router, softmax and norms are float32."""

    vocab_size: int = 512
    hidden: int = 64
    layers: int = 3
    heads: int = 4
    qk_nope_dim: int = 16
    qk_rope_dim: int = 8
    v_dim: int = 16
    kv_rank: int = 32
    dense_ffn: int = 192         # width of the leading dense layers' MLP
    first_dense: int = 1         # leading dense layers before the expert ones
    num_experts: int = 16        # router width
    experts_per_token: int = 4
    expert_ffn: int = 24         # width of one routed expert
    shared_experts: int = 2      # one SwiGLU of shared_experts * expert_ffn
    routed_scale: float = 1.0
    norm_topk: bool = True
    experts_held: tuple[int, int] | None = None   # (first, count); None = all
    rope_theta: float = 1e6
    eps: float = 1e-6
    max_len: int = 512
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    decode_slots: bool = False   # serving: the batch dim is a SLOT table
                                 # (serving/kv_cache.py), positions are the
                                 # caller's

    causal_lm = True
    resumable_step = False       # the one-token step reads every expert a
                                 # token and the table holds latents:
                                 # SlotKVCache builds no program that
                                 # resumes a prompt or moves per-head K/V

    @property
    def expert_layers(self) -> int:
        return max(self.layers - self.first_dense, 0)

    def slot_decode_clone(self, *, partition_model: bool = False,
                          kv_quant: bool = False) -> "LatentMoELM":
        """The module ``SlotKVCache`` serves from."""
        for on, what in ((partition_model, "a tensor-parallel slot table"),
                         (kv_quant, "int8 storage of the latent table")):
            if on:
                raise NotImplementedError(
                    f"{type(self).__name__} does not support {what}")
        return self.clone(decode=True, decode_slots=True)

    @nn.compact
    def __call__(self, token_ids, train: bool = False, positions=None,
                 prompt_len=None):
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
        prefill = prompt_len is not None
        pos = positions if positions is not None \
            else jnp.arange(t, dtype=jnp.int32)[None, :]
        # pad tokens of a prefill bucket go to no expert: their rows are
        # nobody's result
        valid = (jnp.arange(t)[None, :] < prompt_len[:, None]) if prefill \
            else None

        x = nn.Embed(self.vocab_size, self.hidden, dtype=self.dtype,
                     param_dtype=self.param_dtype,
                     name="token_embed")(token_ids)
        attn = dict(hidden=self.hidden, heads=self.heads,
                    qk_nope_dim=self.qk_nope_dim,
                    qk_rope_dim=self.qk_rope_dim, v_dim=self.v_dim,
                    kv_rank=self.kv_rank, rope_theta=self.rope_theta,
                    eps=self.eps, max_len=self.max_len,
                    decode_slots=self.decode_slots, dtype=self.dtype,
                    param_dtype=self.param_dtype)
        moe = dict(num_experts=self.num_experts,
                   top_k=self.experts_per_token, hidden=self.expert_ffn,
                   shared_hidden=self.shared_experts * self.expert_ffn,
                   routed_scale=self.routed_scale, norm_topk=self.norm_topk,
                   held=self.experts_held)
        for i in range(self.layers):
            x = LatentMoEBlock(
                attn, self.dense_ffn, None if i < self.first_dense else moe,
                self.eps, self.dtype, self.param_dtype,
                name=f"block_{i}")(x, pos, prefill, valid)
        if prefill:     # the one position whose logits sample a token
            x = jnp.take_along_axis(
                x, (prompt_len - 1)[:, None, None].astype(jnp.int32), axis=1)
        x = RMSNorm(self.eps, self.dtype, self.param_dtype,
                    name="final_norm")(x)
        logits = nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype,
                          param_dtype=self.param_dtype, name="lm_head")(x)
        return logits.astype(jnp.float32)
