"""Selective-scan (Mamba-1) / multi-query-attention hybrid decoder (the
``jamba`` block family with one expert: every feed-forward dense).

What ``models/hybrid_ssm.HybridSSMLM`` is not: a layer has TWO sub-layers,
a mixer and then a dense SwiGLU, each behind an RMSNorm of its own; the
mixer is attention where ``i % attn_period == attn_offset`` and the
Mamba-1 state-space mixer elsewhere; the head is tied to the embedding.

    layer i:  x = x + Mixer_i(RMS(x));  x = x + W_down(silu(W_gate n) * (W_up n)),  n = RMS'(x)
    logits = RMS_final(x) E^T

The state-space mixer, with ``d_i = expand * hidden`` channels, state ``N``,
step rank ``R`` and ``taps`` convolution taps:

    [u, z]    = x W_in                       (h -> 2 d_i)
    u         = silu(conv(u) + b_conv)       causal, depthwise
    [r, B, C] = u W_x                        (d_i -> R + N + N)
    r, B, C   = RMS_dt(r), RMS_B(B), RMS_C(C)        (a learned gain each)
    dt        = softplus(r W_dt + b_dt)      (R -> d_i), float32
    A         = -exp(A_log)                  (d_i, N), float32
    S_t[d, n] = exp(dt_t[d] A[d, n]) S_{t-1}[d, n] + dt_t[d] u_t[d] B_t[n]
    y_t[d]    = sum_n S_t[d, n] C_t[n] + D[d] u_t[d]
    out       = (y * silu(z)) W_out          (d_i -> h)

Unlike Mamba-2 (``models/hybrid_ssm.py``) the decay differs for every
channel AND every state index, the step comes through a rank-``R``
bottleneck, ``dt``, ``B`` and ``C`` each pass a norm, there are no heads or
groups and no norm after the gate.  No bias anywhere but the convolution's
and ``b_dt``.  Softmax, norms, ``dt``, decays and the state are float32;
matrix products take ``dtype`` operands and ``r W_dt`` accumulates to
float32.  ``A_log``, ``D`` and ``b_dt`` are held in float32.

Two forms of the mixer, held equal by tests/test_jamba.py:

* BLOCK (training-mode forward, prefill): the recurrence in
  ``ops/selective_scan.selective_scan``, one Pallas kernel that keeps the
  state on chip; a position whose ``dt`` is 0 is inert.  A block longer
  than ``SEQ_CHUNK`` positions goes through the mixer (and through the
  feed-forward) in pieces of ``SEQ_CHUNK`` under a ``lax.scan`` that hands
  the state and the convolution's last ``taps - 1`` rows from one piece to
  the next and writes each piece's output over its input.
* STEP (the slot-decode step): ``ops/selective_scan.selective_step``, one
  fused ``jnp`` update of ``(slots, d_i, N)``.

Attention is ``models/hybrid_ssm.GroupedQueryAttention`` (no position term:
order comes from the state-space layers), here at ONE key/value head.

Slot-decode mode keeps the two kinds of leaf of serving/kv_cache.py's
contract: the attention layers' rows ``(slots, max_len, kv_heads,
head_dim)``, and per-slot state named in ``slot_state``: ``ssm_state``
``(slots, d_i, N)`` float32 and ``conv_tail`` ``(slots, taps - 1, d_i)``,
the pre-activation ``u`` rows of the slot's last tokens.  A call with
``prompt_len`` is a PREFILL from position 0 and a ZERO state over the whole
padded bucket (``dt`` = 0 on the pads; the tail written is the rows
``prompt_len - (taps - 1) .. prompt_len - 1``, zeros where the prompt is
shorter; logits for the last real position only); a call without it is the
STEP, one token a slot, with ``active``: a slot that is not active keeps
state and tail bit for bit."""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.models.hybrid_ssm import (
    GroupedQueryAttention, dt_bias_init)
from distributed_tensorflow_tpu.models.mla_moe import RMSNorm
from distributed_tensorflow_tpu.models.moe import SwiGLU
from distributed_tensorflow_tpu.ops.selective_scan import (
    selective_scan, selective_step)


def a_log_init(key, shape, dtype=jnp.float32):
    """``A[d, n] = -(n + 1)``: the family's initialisation."""
    del key
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)),
        shape).astype(dtype)


# positions of a block that one piece of the mixer (and of the feed-forward)
# takes: a 32,768 bucket in one piece needs 12.6 GB of temporaries on the
# v5e (385 KB a position: the compiler's count), which beside the weights
# and the table does not fit; pieces of 4,096 carry the state and the
# convolution's last rows from one to the next (PERF.md section 6)
SEQ_CHUNK = 4096


def _pieces(t: int) -> int:
    """How many pieces a block of ``t`` positions is taken in."""
    return t // SEQ_CHUNK if t > SEQ_CHUNK and t % SEQ_CHUNK == 0 else 1


class SelectiveMixer(nn.Module):
    """The state-space mixer (module docstring: both forms).  Its matrices
    are plain parameters, not ``nn.Dense`` submodules: the block form runs
    inside a ``lax.scan`` over the block's pieces."""

    hidden: int
    inner: int              # d_i
    state: int              # N
    dt_rank: int            # R
    taps: int
    eps: float
    decode_slots: bool
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, prompt_len, active):
        bsz, t, _ = x.shape
        di, n, rank, taps = self.inner, self.state, self.dt_rank, self.taps
        f32 = jnp.float32

        def matrix(name, shape):
            return self.param(name, nn.initializers.lecun_normal(), shape,
                              self.param_dtype)

        def vector(name, init, shape, held=f32):
            return self.param(name, init, shape, held).astype(f32)

        def product(v, w):      # ``dtype`` operands and result, as nn.Dense
            return jnp.dot(v.astype(self.dtype), w.astype(self.dtype))

        def rms(v, g):          # float32 out: B and C feed the recurrence
            v = v.astype(f32)
            return v * lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                 + self.eps) * g

        ones = nn.initializers.ones_init()
        w_in, w_out = matrix("in_proj", (self.hidden, 2 * di)), \
            matrix("out_proj", (di, self.hidden))
        w_x, w_dt = matrix("x_proj", (di, rank + 2 * n)), \
            matrix("dt_proj", (rank, di))
        conv_w = vector("conv_weight", nn.initializers.lecun_normal(),
                        (taps, di), self.param_dtype)
        conv_b = vector("conv_bias", nn.initializers.zeros_init(), (di,),
                        self.param_dtype)
        gains = [vector(name, ones, (size,), self.param_dtype)
                 for name, size in (("dt_norm", rank), ("b_norm", n),
                                    ("c_norm", n))]
        a = -jnp.exp(vector("A_log", a_log_init, (di, n)))
        skip = vector("D", ones, (di,))
        dt_b = vector("dt_bias", dt_bias_init(0.001, 0.1, 1e-4), (di,))

        def selective(conv):
            """Pre-activation convolution output -> ``u`` and the step's
            ``dt`` (float32), ``B``, ``C``."""
            u = jax.nn.silu(conv).astype(self.dtype)
            low = product(u, w_x)
            r, b, c = (rms(part, g) for g, part in zip(gains, (
                low[..., :rank], low[..., rank:rank + n],
                low[..., rank + n:])))
            dt = jnp.dot(r.astype(self.dtype), w_dt.astype(self.dtype),
                         preferred_element_type=f32)
            return u, jax.nn.softplus(dt + dt_b), b, c

        def gated(y, z):
            return product((y * jax.nn.silu(z.astype(f32))), w_out)

        def piece(xs, history, start, valid):
            """One piece of a block: ``xs`` (B, C, h) after ``history``,
            the ``taps - 1`` pre-activation rows before it, from the state
            ``start``.  Returns the mixer's output, the pre-activation rows
            with ``history`` before them, and the state after the piece."""
            proj = product(xs, w_in)
            pre, z = proj[..., :di], proj[..., di:]
            rows = jnp.concatenate([history.astype(pre.dtype), pre], axis=1)
            conv = sum(conv_w[j] * rows[:, j:j + xs.shape[1]].astype(f32)
                       for j in range(taps)) + conv_b
            u, dt, b, c = selective(conv)
            if valid is not None:       # the bucket's pads stand still
                dt = jnp.where(valid[..., None], dt, 0.0)
            y, last = selective_scan(u, dt, a, b, c, skip, start)
            return gated(y, z), rows, last

        def tail_of(rows, first, before):
            """The rows of positions ``first .. first + taps - 2`` out of
            ``rows`` (whose row 0 is position ``before - (taps - 1)``)."""
            at = jnp.clip((first - before)[:, None] + jnp.arange(taps - 1),
                          0, rows.shape[1] - 1)
            return jnp.take_along_axis(rows, at[..., None], axis=1)

        def block(valid):
            """The kernel over the block from a zero state and tail, in
            ``_pieces(t)`` pieces; also the state after it and, for a
            prefill, the pre-activation rows of the prompt's last ``taps -
            1`` positions (zeros before position 0)."""
            history = jnp.zeros((bsz, taps - 1, di), self.dtype)
            state = jnp.zeros((bsz, di, n), f32)
            count = _pieces(t)
            if count == 1:
                out, rows, last = piece(x, history, state, valid)
                tail = None if prompt_len is None else tail_of(
                    rows, prompt_len, jnp.zeros_like(prompt_len))
                return out, last, tail
            size = t // count

            def body(carry, before):
                # the piece's input is read out of ``rows_io`` and its
                # output written over it: the block's one buffer (a stacked
                # scan output is zero-filled before the loop, and the v5e's
                # compiler makes every layer's at the program's start:
                # 9 GB at 32,768)
                rows_io, history, state, tail = carry
                xs = lax.dynamic_slice_in_dim(rows_io, before, size, axis=1)
                ok = None if valid is None else lax.dynamic_slice_in_dim(
                    valid, before, size, axis=1)
                out, rows, last = piece(xs, history, state, ok)
                if prompt_len is not None:
                    # the piece that holds the prompt's last token
                    mine = (before < prompt_len) & (prompt_len
                                                    <= before + size)
                    tail = jnp.where(mine[:, None, None],
                                     tail_of(rows, prompt_len, before), tail)
                rows_io = lax.dynamic_update_slice_in_dim(
                    rows_io, out.astype(rows_io.dtype), before, axis=1)
                return (rows_io, rows[:, -(taps - 1):], last, tail), None

            (out, _, last, tail), _ = lax.scan(
                body, (x, history, state, history),
                jnp.arange(count) * size)
            return out, last, tail

        if not self.decode_slots:
            return block(None)[0]
        # has_variable is False exactly during .init(): create the state,
        # write nothing (models/gpt.py's guard)
        ready = self.has_variable("cache", "ssm_state")
        sv = self.variable("cache", "ssm_state", jnp.zeros, (bsz, di, n), f32)
        tv = self.variable("cache", "conv_tail", jnp.zeros,
                           (bsz, taps - 1, di), self.dtype)
        if not ready:
            return block(None)[0]
        if prompt_len is not None:
            # PREFILL: from zero, pads inert; what the slot held is not read
            out, last, tail = block(jnp.arange(t)[None, :]
                                    < prompt_len[:, None])
            sv.value = last
            tv.value = tail.astype(tv.value.dtype)
            return out
        if t != 1:
            raise ValueError(
                "the state-space step takes one token a slot: a token "
                "block cannot be scored against a recurrent state and "
                "taken back")
        # STEP: the window is the tail and this token's row
        proj = product(x, w_in)
        pre, z = proj[..., :di], proj[..., di:]
        window = jnp.concatenate([tv.value.astype(pre.dtype), pre], axis=1)
        conv = jnp.einsum("bkw,kw->bw", window.astype(f32), conv_w) + conv_b
        u, dt, b, c = selective(conv)
        y, new = selective_step(sv.value, u, dt, a, b, c, skip)
        keep = active if active is not None else jnp.ones((bsz,), bool)
        sv.value = jnp.where(keep[:, None, None], new, sv.value)
        tv.value = jnp.where(keep[:, None, None],
                             window[:, 1:].astype(tv.value.dtype), tv.value)
        return gated(y[:, None], z)


def _in_pieces(module: nn.Module, x):
    """``module`` (row-wise: a feed-forward) over ``x`` (B, L, h), a piece
    of ``SEQ_CHUNK`` positions at a time where the block is longer."""
    bsz, t, h = x.shape
    count = _pieces(t)
    if count == 1:
        return module(x)
    size = t // count

    def body(m, rows_io, before):   # in place: see SelectiveMixer's block
        xs = lax.dynamic_slice_in_dim(rows_io, before, size, axis=1)
        return lax.dynamic_update_slice_in_dim(
            rows_io, m(xs).astype(rows_io.dtype), before, axis=1), None

    scan = nn.scan(body, variable_broadcast="params",
                   split_rngs={"params": False})
    return scan(module, x, jnp.arange(count) * size)[0]


class JambaLM(nn.Module):
    """Decoder-only LM of the blocks above: token ids (B, L) -> next-token
    logits (B, L, V) in float32.

    ``param_dtype`` is what the weights are held in, ``dtype`` what the
    matrix products run in."""

    vocab_size: int = 512
    hidden: int = 64
    layers: int = 4
    attn_period: int = 4         # attention where i % period == offset
    attn_offset: int = 2
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 4
    heads: int = 4
    kv_heads: int = 1
    head_dim: int = 16
    ffn: int = 128
    eps: float = 1e-6
    max_len: int = 512
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    decode_slots: bool = False   # serving: the batch dim is a SLOT table
                                 # (serving/kv_cache.py), positions are the
                                 # caller's

    causal_lm = True
    resumable_step = False       # the state has no positions to resume at
    # the per-slot state leaves of the ``cache`` collection, and whether
    # the table may narrow them to its ``kv_dtype``
    slot_state = {"ssm_state": False, "conv_tail": True}

    def is_attention(self, i: int) -> bool:
        return i % self.attn_period == self.attn_offset

    @property
    def selective_scan_layers(self) -> int:
        """The layers whose block form runs ``ops/selective_scan``."""
        return sum(not self.is_attention(i) for i in range(self.layers))

    def slot_decode_clone(self, *, partition_model: bool = False,
                          kv_quant: bool = False) -> "JambaLM":
        """The module ``SlotKVCache`` serves from."""
        for on, what in ((partition_model, "a tensor-parallel slot table"),
                         (kv_quant, "int8 storage of the table")):
            if on:
                raise NotImplementedError(
                    f"{type(self).__name__} does not support {what}")
        return self.clone(decode=True, decode_slots=True)

    @nn.compact
    def __call__(self, token_ids, train: bool = False, positions=None,
                 prompt_len=None, active=None):
        _, t = token_ids.shape
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

        kinds = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        norm = lambda name: RMSNorm(self.eps, **kinds, name=name)
        embed = nn.Embed(self.vocab_size, self.hidden, **kinds,
                         name="token_embed")
        x = embed(token_ids)
        for i in range(self.layers):
            y = norm(f"norm_{i}")(x)
            if self.is_attention(i):
                y = GroupedQueryAttention(
                    self.hidden, self.heads, self.kv_heads, self.head_dim,
                    self.max_len, self.decode_slots, **kinds,
                    name=f"mixer_{i}")(y, pos, prefill)
            else:
                y = SelectiveMixer(
                    self.hidden, self.ssm_expand * self.hidden,
                    self.ssm_state, self.ssm_dt_rank, self.ssm_conv,
                    self.eps, self.decode_slots, **kinds,
                    name=f"mixer_{i}")(y, prompt_len, active)
            x = x + y
            x = x + _in_pieces(SwiGLU(self.ffn, **kinds, name=f"ffn_{i}"),
                               norm(f"ffn_norm_{i}")(x))
        if prefill:     # the one position whose logits sample a token
            x = jnp.take_along_axis(
                x, (prompt_len - 1)[:, None, None].astype(jnp.int32), axis=1)
        return embed.attend(norm("final_norm")(x)).astype(jnp.float32)
