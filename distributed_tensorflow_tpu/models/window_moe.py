"""Window-and-global-attention, sparse-expert decoder with a parallel block
(the ``cohere2_moe`` block family).

What the three other served decoders are not: a bias-free LayerNorm (mean
taken out), ONE norm a layer whose output both branches read, ``x = x +
Attn_i(n) + FFN(n)`` with ``n = LN_i(x)``; grouped-query attention whose
layers are of two kinds, chosen a layer by a pattern string:

* ``W``, a WINDOW layer: q and k rotated by position over the whole head,
  adjacent pairs ``(2j, 2j + 1)`` (``models/mla_moe.rope_adjacent``), and
  query ``t`` sees the keys ``t - window < s <= t``;
* ``F``, a FULL layer: no position term at all, query ``t`` sees ``s <= t``.

``FFN`` is ``models/moe.DroplessMoE``: sigmoid scores, top-k, weights over
their sum, SwiGLU experts, and ``shared_experts`` shared experts whose
outputs are AVERAGED.  A final LayerNorm, then the TIED head ``logits =
logit_scale * x E^T``.

Attention over a block of tokens (training-mode forward, prefill) is
``window_attention_blocked``: query blocks against key blocks with the
softmax carried across the key blocks, and a key block that lies wholly
outside a query block's window (or after it) is SKIPPED, so a window layer
costs ``window / L`` of a full one at long ``L``.

Slot-decode mode (``decode=True, decode_slots=True``, what
``serving/kv_cache.SlotKVCache`` clones a model into) keeps TWO kinds of
per-position leaf in the ``cache`` collection (the contract is at the top
of serving/kv_cache.py):

* full-length rows ``cached_key`` / ``cached_value`` ``(slots, max_len,
  kv_heads, head_dim)`` in the ``F`` layers, as ``models/hybrid_ssm.py``;
* RINGS ``ring_key`` / ``ring_value`` ``(slots, ring, kv_heads,
  head_dim)`` in the ``W`` layers, ``ring = min(window, max_len)``, named
  with their length in ``slot_rings``: position ``p`` lives in row ``p mod
  ring``, keys are stored ROTATED (so the order of rows does not matter),
  and rows ``0 .. min(length, ring) - 1`` are valid.

A call with ``prompt_len`` is a PREFILL from position 0: the block attends
within itself under the window mask; a ring takes exactly the rows of
positions ``max(0, n - ring) .. n - 1`` of a prompt of ``n`` tokens (one
gather from the block's keys) and nothing for the bucket's pads, whose
position ``p`` would land on the row of the real position ``p - ring``; a
ring row that no prompt position falls on keeps what it held, invisible
until the step that writes it.  A call without it is the STEP, one token a
slot: a ring takes the row ``length mod ring`` and is attended up to row
``min(length + 1, ring) - 1``.  A slot that is free or sits the round out
writes the row of its own next position, which its next real write covers
and which nothing reads before (the key it replaces left the window of
every query still to come); the step is handed ``active`` and sends such
a slot's stale token to no expert (24 free slots that all hold token 0 at
position 0 would otherwise be routed alike and have their experts read
every round).
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.models.gpt import write_slot_rows
from distributed_tensorflow_tpu.models.mla_moe import rope_adjacent
from distributed_tensorflow_tpu.models.moe import DroplessMoE
from distributed_tensorflow_tpu.models.slot_contract import SlotContract

ATTN_BLOCK = 512            # query and key block of the blocked attention
MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)   # a score no key has


class LayerNorm(nn.Module):
    """``(x - mean) / sqrt(var + eps) * g`` in float32, no offset."""

    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.ones_init(), (x.shape[-1],),
                       self.param_dtype)
        x = x.astype(jnp.float32)
        x = x - jnp.mean(x, -1, keepdims=True)
        y = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.eps)
        return (y * g.astype(jnp.float32)).astype(self.dtype)


def window_attention_blocked(q, k, v, scale: float, window: int | None,
                             block: int = ATTN_BLOCK):
    """Causal grouped-query softmax attention from position 0, under a
    window, in query blocks against key blocks.

    ``q``: (B, L, Hk, G, d), query head ``(h, g)`` reads key/value head
    ``h``; ``k``, ``v``: (B, L, Hk, d).  Query ``t`` sees the keys ``s <=
    t`` and, with a ``window``, ``s > t - window``.  Two nested scans over
    blocks of ``block`` positions: the softmax is carried across a query
    block's key blocks (running row maximum, row sum and unnormalised
    output, float32), so the one score tile alive is ``(B, Hk, G, block,
    block)``; a key block no query of the block sees (it lies after the
    block, or wholly before its window) is skipped by a conditional, so
    the work is the causal band and not the square."""
    b, length, hk, g, d = q.shape
    size = min(block, length)
    pad = -length % size
    if pad:     # pad keys lie after every real query: never seen
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                   for t in (q, k, v))
    n = (length + pad) // size
    blocks = lambda t: jnp.moveaxis(
        t.reshape((b, n, size) + t.shape[2:]), 1, 0)
    key_blocks = (blocks(k), blocks(v), jnp.arange(n))
    at = jnp.arange(size)

    def query_block(_, inp):
        qi, i = inp
        t_pos = i * size + at                                   # (size,)

        def key_block(carry, kin):
            kj, vj, j = kin
            seen = j <= i
            if window is not None:      # its last key against query i's first
                seen = seen & ((j + 1) * size - 1 > i * size - window)

            def attend(carry):
                m, l, acc = carry
                s = jnp.einsum("bqhgd,bshd->bhgqs", qi, kj,
                               preferred_element_type=jnp.float32) * scale
                s_pos = j * size + at
                mask = s_pos[None, :] <= t_pos[:, None]
                if window is not None:
                    mask = mask & (s_pos[None, :] > t_pos[:, None] - window)
                s = jnp.where(mask, s, MASKED)
                # a row that has met no key yet sums garbage at m = MASKED;
                # its first real key sets m, and exp(MASKED - m) = 0 wipes it
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                a = jnp.exp(m - m_new)
                pv = jnp.einsum("bhgqs,bshd->bhgqd", p.astype(vj.dtype), vj,
                                preferred_element_type=jnp.float32)
                return (m_new, l * a + jnp.sum(p, axis=-1),
                        acc * a[..., None] + pv)

            return lax.cond(seen, attend, lambda c: c, carry), None

        start = (jnp.full((b, hk, g, size), MASKED, jnp.float32),
                 jnp.zeros((b, hk, g, size), jnp.float32),
                 jnp.zeros((b, hk, g, size, v.shape[-1]), jnp.float32))
        (_, l, acc), _ = lax.scan(key_block, start, key_blocks)
        out = (acc / l[..., None]).astype(v.dtype)              # (b,h,g,q,d)
        return None, jnp.transpose(out, (0, 3, 1, 2, 4))

    _, out = lax.scan(query_block, None, (blocks(q), jnp.arange(n)))
    out = jnp.moveaxis(out, 0, 1).reshape((b, n * size, hk, g, v.shape[-1]))
    return out[:, :length]


class WindowAttention(nn.Module):
    """Grouped-query attention of one layer, of either kind: ``window`` and
    ``rope_theta`` set (a ``W`` layer) or both None (an ``F`` layer)."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    window: int | None
    rope_theta: float | None
    max_len: int
    decode_slots: bool
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, pos, prompt_len):
        bsz, t, _ = x.shape
        hq, hk, d = self.heads, self.kv_heads, self.head_dim
        scale = 1.0 / math.sqrt(d)

        def dense(size, name):
            return nn.Dense(size, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        q = dense(hq * d, "q_proj")(x).reshape(bsz, t, hq, d)
        k = dense(hk * d, "k_proj")(x).reshape(bsz, t, hk, d)
        v = dense(hk * d, "v_proj")(x).reshape(bsz, t, hk, d)
        if self.rope_theta is not None:
            q = rope_adjacent(q, pos, self.rope_theta)
            k = rope_adjacent(k, pos, self.rope_theta)
        q = q.reshape(bsz, t, hk, hq // hk, d)
        out = dense(self.hidden, "o_proj")

        def within():
            """The block attends within itself from position 0."""
            o = window_attention_blocked(q, k, v, scale, self.window)
            return out(o.reshape(bsz, t, hq * d))

        if not self.decode_slots:
            return within()
        ring = self.window is not None
        rows = min(self.window, self.max_len) if ring else self.max_len
        names = ("ring_key", "ring_value") if ring \
            else ("cached_key", "cached_value")
        # has_variable is False exactly during .init(): create the table,
        # write nothing (models/gpt.py's guard)
        ready = self.has_variable("cache", names[0])
        ck, cv = (self.variable("cache", name, jnp.zeros,
                                (bsz, rows, hk, d), self.dtype)
                  for name in names)
        if not ready:
            return within()
        if prompt_len is not None:
            for var, new in ((ck, k), (cv, v)):
                new = new.astype(var.value.dtype)
                if ring:
                    new = _ring_rows(var.value, new, prompt_len)
                # F: one piece from position 0; pad rows past the prompt
                # hold keys of pad tokens, invisible under the length mask
                var.value = lax.dynamic_update_slice_in_dim(
                    var.value, new, 0, axis=1)
            return within()
        if ring and t != 1:
            raise ValueError(
                "the step over a ring takes one token a slot: a token "
                "block would overwrite rows its own queries still see")
        at = pos % rows if ring else pos
        ck.value = write_slot_rows(ck.value, k.astype(ck.value.dtype), at)
        cv.value = write_slot_rows(cv.value, v.astype(cv.value.dtype), at)
        keys, values = (ck.value.astype(self.dtype),
                        cv.value.astype(self.dtype))
        s = jnp.einsum("bthgd,blhd->bhgtl", q, keys,
                       preferred_element_type=jnp.float32) * scale
        # rows up to the position just written; in a ring, all of them
        # once the positions have wrapped
        upto = jnp.minimum(pos, rows - 1) if ring else pos
        valid = jnp.arange(rows)[None, None, :] <= upto[:, :, None]
        prob = jax.nn.softmax(
            jnp.where(valid[:, None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhgtl,blhd->bthgd", prob.astype(self.dtype), values)
        return out(o.reshape(bsz, t, hq * d))


def _ring_rows(held, new, prompt_len):
    """What a prefill puts into a ring's first ``min(ring, L)`` rows:
    ``held`` (B, ring, ...) is the ring, ``new`` (B, L, ...) the block's
    rows at positions ``0 .. L - 1``, ``prompt_len`` (B,) how many of them
    are the prompt's.  Row ``r`` takes the LAST prompt position that falls
    on it, ``r + ring * ((n - 1 - r) // ring)``; a row no prompt position
    falls on (``r >= n``) keeps what it held: a pad writes nothing."""
    ring, length = held.shape[1], new.shape[1]
    r = jnp.arange(min(ring, length))[None, :]
    n = prompt_len[:, None]
    at = jnp.clip(r + ring * ((n - 1 - r) // ring), 0, length - 1)
    tail = (slice(None),) * 2 + (None,) * (new.ndim - 2)
    return jnp.where((r < n)[tail], jnp.take_along_axis(new, at[tail], 1),
                     held[:, :r.shape[1]])


class WindowMoELM(nn.Module):
    """Decoder-only LM of the blocks above: token ids (B, L) -> next-token
    logits (B, L, V) in float32.

    ``param_dtype`` is what the weights are held in, ``dtype`` what the
    matrix products run in; router, softmax and norms are float32."""

    vocab_size: int = 512
    hidden: int = 64
    pattern: str = "WWWF"        # a layer: W (window, rotary) or F (full,
                                 # no position term)
    window: int = 16
    rope_theta: float = 50000.0
    heads: int = 8               # query heads
    kv_heads: int = 2
    head_dim: int = 16
    num_experts: int = 16        # router width
    experts_per_token: int = 4
    expert_ffn: int = 32         # width of one routed expert
    shared_experts: int = 2      # averaged
    shared_ffn: int = 32         # width of ONE shared expert
    norm_topk: bool = True
    experts_held: tuple[int, int] | None = None   # (first, count); None = all
    moe_token_block: int = 2048  # DroplessMoE.token_block
    logit_scale: float = 1.0
    eps: float = 1e-5
    max_len: int = 512
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    decode_slots: bool = False   # serving: the batch dim is a SLOT table
                                 # (serving/kv_cache.py), positions are the
                                 # caller's

    causal_lm = True

    @property
    def slot_rings(self) -> dict[str, int]:
        """The ring leaves of the ``cache`` collection and their rows (the
        contract is at the top of serving/kv_cache.py)."""
        if "W" not in self.pattern:
            return {}
        rows = min(self.window, self.max_len)
        return {"ring_key": rows, "ring_value": rows}

    def slot_decode_clone(self, *, mesh=None,
                          kv_quant: bool = False) -> "WindowMoELM":
        """The module ``SlotKVCache`` serves from: the same under any
        ``mesh`` (replicated over a 'model' axis), and with float rows
        (its contract declares no int8 rows, so the table refuses
        ``kv_quant``)."""
        return self.clone(decode=True, decode_slots=True)

    def slot_contract(self) -> SlotContract:
        """Rings beside the full-length rows (a ring holds no position to
        resume at); a model with rings reports ``past_window`` for a round
        and ``ring_rows`` for a prefill."""
        rings = self.slot_rings
        hooks = dict(round_attrs=self.past_window,
                     prefill_attrs=self.ring_rows) if rings else {}
        return SlotContract(
            takes_active=True, slot_rings=rings,
            expert_layers=len(self.pattern), num_experts=self.num_experts,
            experts_per_token=self.experts_per_token,
            experts_held=self.experts_held, **hooks)

    def past_window(self, lengths, advancing) -> dict[str, int]:
        """The ``advancing`` slots whose length (host array ``lengths``)
        exceeds the rings' rows: whose window layers no longer hold every
        position."""
        ring = min(self.slot_rings.values())
        return {"past_window": int((lengths[advancing] > ring).sum())}

    def ring_rows(self, prompt_len: int) -> dict[str, int]:
        """The rows a prefill of ``prompt_len`` tokens writes into each
        ring."""
        return {"ring_rows": min(prompt_len, *self.slot_rings.values())}

    @nn.compact
    def __call__(self, token_ids, train: bool = False, positions=None,
                 prompt_len=None, active=None):
        b, t = token_ids.shape
        if set(self.pattern) - set("WF") or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: W or F a layer")
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
            else jnp.arange(t, dtype=jnp.int32)[None, :]
        # pad tokens of a prefill bucket, and the stale tokens of slots
        # that sit a round out (``active``, the step's), go to no expert
        if prompt_len is not None:
            routed = jnp.arange(t)[None, :] < prompt_len[:, None]
        else:
            routed = None if active is None \
                else jnp.broadcast_to(active[:, None], (b, t))

        kinds = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        embed = nn.Embed(self.vocab_size, self.hidden, **kinds,
                         name="token_embed")
        x = embed(token_ids)
        for i, kind in enumerate(self.pattern):
            n = LayerNorm(self.eps, **kinds, name=f"norm_{i}")(x)
            windowed = kind == "W"
            attn = WindowAttention(
                self.hidden, self.heads, self.kv_heads, self.head_dim,
                self.window if windowed else None,
                self.rope_theta if windowed else None, self.max_len,
                self.decode_slots, **kinds, name=f"attn_{i}")(
                    n, pos, prompt_len)
            ffn = DroplessMoE(
                num_experts=self.num_experts, top_k=self.experts_per_token,
                hidden=self.expert_ffn,
                shared_hidden=self.shared_experts * self.shared_ffn,
                shared_experts=self.shared_experts, norm_topk=self.norm_topk,
                held=self.experts_held, token_block=self.moe_token_block,
                **kinds, name=f"ffn_{i}")(
                    n.reshape(b * t, self.hidden),
                    None if routed is None else routed.reshape(b * t)
            ).reshape(b, t, self.hidden)
            x = x + attn + ffn
        if prompt_len is not None:  # the one position whose logits sample
            x = jnp.take_along_axis(
                x, (prompt_len - 1)[:, None, None].astype(jnp.int32), axis=1)
        x = LayerNorm(self.eps, **kinds, name="final_norm")(x)
        return (embed.attend(x) * self.logit_scale).astype(jnp.float32)
