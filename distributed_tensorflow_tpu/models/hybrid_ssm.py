"""Hybrid state-space / attention / latent-expert decoder (the
``nemotron_h`` block family).

What ``models/gpt.GPTLM`` and ``models/mla_moe.LatentMoELM`` are not: a
block is ONE mixer, ``x = x + Mixer_i(RMS_i(x))``, chosen a layer by a
pattern string (``hybrid_override_pattern``): ``M`` a Mamba-2 state-space
mixer, ``*`` grouped-query attention with no position term (order comes
from the state-space layers), ``E`` a sigmoid-routed expert layer whose
experts are two-matrix squared-ReLU MLPs in a latent of the hidden state
(``models/moe.DroplessMoE`` with ``expert_act="relu2"`` and ``latent``).
RMSNorm with a plain gain, no biases but the convolution's, a final norm
and an untied head.

``M``, with ``H`` heads of ``P``, ``G`` groups, state ``N``, ``d_i = H P``:

    [z, xBC, dt] = x W_in            (h -> d_i + (d_i + 2 G N) + H)
    xBC = silu(conv(xBC) + b)        causal, depthwise, ``conv_kernel`` taps
    [x_s, B, C] = xBC                (H x P, G x N, G x N)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)   (a scalar a head)
    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T    (P x N a head, S_0 = 0)
    y_t = S_t C_t + D_h x_t
    out = GroupRMS(y silu(z)) W_out  (gate first, then the norm; d_i -> h)

The recurrence has two forms of one function (tests/test_hybrid_ssm.py
holds them equal, and both equal to the plain scan of the reference):

* CHUNKED (``ssd_chunked``: training-mode forward, prefill): within a
  chunk of ``chunk`` tokens the quadratic form ``Y = (L . C B^T) (dt x)``
  with ``L[l, s] = exp(sum_{s < i <= l} dt_i A)``; across chunks the
  recurrence on ``S``, one step a chunk.  A position whose ``dt`` is 0
  leaves ``S`` as it is and adds nothing: that is how a bucket's pad rows
  are made inert.
* STEP (``ssd_step``: the slot-decode step): one update of ``S`` a token.

Slot-decode mode (``decode=True, decode_slots=True``, what
``serving/kv_cache.SlotKVCache`` clones a model into) keeps TWO kinds of
leaf in the ``cache`` collection (the contract is at the top of
serving/kv_cache.py):

* per-position rows ``(slots, max_len, kv_heads, head_dim)``: the
  attention layer's keys and values, written through ``write_slot_rows``,
  valid up to the caller's position;
* per-slot state, named in the slot contract's ``slot_state``:
  ``ssm_state`` ``(slots, H, P, N)`` float32 (an error in ``S`` is carried
  through every later token) and ``conv_tail`` ``(slots, taps - 1, d_i +
  2 G N)``, the pre-activation ``xBC`` rows of the slot's last tokens.

A call with ``prompt_len`` is a PREFILL from position 0 and from a ZERO
state: the chunked scan over the whole padded block with pads inert, so
that the state written is the state after ``prompt_len`` tokens and the
tail the rows ``prompt_len-3 .. prompt_len-1`` (zeros where the prompt is
shorter); what the slot held is not read.  A call without it is the STEP,
one token a slot, with ``active`` (slots,): a slot that is not active
keeps its state and tail bit for bit (its rows of the attention table
take the invisible write at its length, as in models/gpt.py), and its
token goes to no expert.

Not built: the multi-token-prediction head of the published checkpoints
(it drafts tokens for speculative decoding; the logits served are the same
without it).
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.models.gpt import write_slot_rows
from distributed_tensorflow_tpu.models.mla_moe import (
    RMSNorm, causal_attention_blocked)
from distributed_tensorflow_tpu.models.moe import DroplessMoE
from distributed_tensorflow_tpu.models.slot_contract import SlotContract
from distributed_tensorflow_tpu.models.window_moe import (
    window_attention_blocked)

# the longest block ``GroupedQueryAttention`` takes in unrolled pieces
ATTN_UNROLLED_MAX = 16384


def _per_head(t, heads: int):
    """``(..., G, N)`` -> ``(..., H, N)``: head ``h`` reads group
    ``h // (H / G)``."""
    return jnp.repeat(t, heads // t.shape[-2], axis=-2)


def ssd_chunked(x, dt, a, b, c, chunk: int, dtype=jnp.float32):
    """The state-space recurrence from a zero state, a chunk at a time.

    ``x`` (B, L, H, P); ``dt`` (B, L, H) after the softplus, 0 where the
    position is a pad; ``a`` (H,) negative; ``b``, ``c`` (B, L, G, N).
    Returns ``S_t C_t`` (B, L, H, P) float32, without ``D x``, and the
    state after the last position (B, H, P, N) float32.  Decays and sums
    are float32; the three big products take ``dtype`` operands."""
    bsz, length, heads, p = x.shape
    groups, n = b.shape[2:]
    q = min(chunk, length)
    pad = -length % q
    if pad:     # dt = 0 there: the state stands still
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc, hg = (length + pad) // q, heads // groups
    # chunk-major with the chunk's positions next to last: (B, c, G, h, Q, .)
    dt = dt.astype(jnp.float32).reshape(bsz, nc, q, groups, hg) \
        .transpose(0, 1, 3, 4, 2)
    fed = x.astype(jnp.float32).reshape(bsz, nc, q, groups, hg, p) \
        .transpose(0, 1, 3, 4, 2, 5) * dt[..., None]            # dt x
    b, c = (t.reshape(bsz, nc, q, groups, n).transpose(0, 1, 3, 2, 4)
            .astype(dtype) for t in (b, c))                     # (B, c, G, Q, N)
    cum = jnp.cumsum(dt * a.reshape(groups, hg, 1), axis=-1)    # (B, c, G, h, Q)

    # within a chunk: L[l, s] = exp(cum_l - cum_s) for s <= l
    diff = cum[..., :, None] - cum[..., None, :]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((q, q), bool)), diff,
                              -jnp.inf))
    cb = jnp.einsum("bcgln,bcgsn->bcgls", c, b,
                    preferred_element_type=jnp.float32)
    y = jnp.einsum("bcghls,bcghsp->bcghlp",
                   (decay * cb[:, :, :, None]).astype(dtype),
                   fed.astype(dtype), preferred_element_type=jnp.float32)

    # what each chunk adds to the state by its end, and its whole decay
    to_end = jnp.exp(cum[..., -1:] - cum)
    added = jnp.einsum("bcghsp,bcgsn->bcghpn",
                       (fed * to_end[..., None]).astype(dtype), b,
                       preferred_element_type=jnp.float32)
    whole = jnp.exp(cum[..., -1])                               # (B, c, G, h)

    def carry(s, inp):
        add, dec = inp
        return dec[..., None, None] * s + add, s    # emits the chunk's START

    last, starts = lax.scan(
        carry, jnp.zeros((bsz, groups, hg, p, n), jnp.float32),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                 # (B, c, G, h, P, N)
    y = y + jnp.einsum("bcgln,bcghpn->bcghlp", c, starts.astype(dtype),
                       preferred_element_type=jnp.float32) \
        * jnp.exp(cum)[..., None]
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(bsz, nc * q, heads, p)
    return y[:, :length], last.reshape(bsz, heads, p, n)


def ssd_step(state, x, dt, a, b, c):
    """One token: ``state`` (B, H, P, N) float32, ``x`` (B, H, P), ``dt``
    (B, H), ``b``, ``c`` (B, G, N) -> ``S_t C_t`` (B, H, P) float32 and the
    new state."""
    heads = x.shape[1]
    dt = dt.astype(jnp.float32)
    b, c = (_per_head(t.astype(jnp.float32), heads) for t in (b, c))
    fed = (dt[..., None] * x.astype(jnp.float32))[..., None] * b[:, :, None]
    state = jnp.exp(dt * a)[..., None, None] * state + fed
    return jnp.einsum("bhpn,bhn->bhp", state, c), state


def dt_bias_init(dt_min: float, dt_max: float, dt_floor: float):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in
    ``[dt_min, dt_max]`` (``time_step_min/max/floor`` of the family)."""
    def init(key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(dt_max) - math.log(dt_min))
                     + math.log(dt_min))
        dt = jnp.maximum(dt, dt_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def a_log_init(key, shape, dtype=jnp.float32):
    """``A = -exp(A_log)`` uniform in ``[-16, -1]``."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


class MambaMixer(nn.Module):
    """The ``M`` mixer (module docstring: both forms)."""

    hidden: int
    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int
    chunk: int
    dt_limits: tuple[float, float, float]
    eps: float
    decode_slots: bool
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, prompt_len, active):
        bsz, t, _ = x.shape
        hn, p, g, n, taps = (self.heads, self.head_dim, self.groups,
                             self.state, self.conv_kernel)
        di, width = hn * p, hn * p + 2 * g * n

        def dense(size, name):
            return nn.Dense(size, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        def vector(name, init, shape=(hn,), held=jnp.float32):
            # a head's dt_bias, A_log and D are float32 whatever the
            # weights are held in: they set the decay of the state
            return self.param(name, init, shape, held).astype(jnp.float32)

        proj = dense(di + width + hn, "in_proj")(x)
        z, xbc, dt = (proj[..., :di], proj[..., di:di + width],
                      proj[..., di + width:])
        conv_w = vector("conv_weight", nn.initializers.lecun_normal(),
                        (taps, width), self.param_dtype)
        conv_b = vector("conv_bias", nn.initializers.zeros_init(), (width,),
                        self.param_dtype)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + vector("dt_bias", dt_bias_init(*self.dt_limits)))
        a = -jnp.exp(vector("A_log", a_log_init))
        skip = vector("D", nn.initializers.ones_init())
        gain = vector("norm", nn.initializers.ones_init(), (di,),
                      self.param_dtype)

        def split(act):
            """Activated ``xBC`` -> ``x_s``, ``B``, ``C``."""
            lead = act.shape[:-1]
            return (act[..., :di].reshape(lead + (hn, p)),
                    act[..., di:di + g * n].reshape(lead + (g, n)),
                    act[..., di + g * n:].reshape(lead + (g, n)))

        def block(valid):
            """The chunked form over the block from a zero state; also the
            pre-activation rows with ``taps - 1`` zero rows before them."""
            rows = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
            conv = sum(conv_w[j] * rows[:, j:j + t].astype(jnp.float32)
                       for j in range(taps)) + conv_b
            xs, b, c = split(jax.nn.silu(conv).astype(self.dtype))
            step = dt if valid is None else jnp.where(valid[..., None], dt, 0)
            y, last = ssd_chunked(xs, step, a, b, c, self.chunk, self.dtype)
            return y + skip[:, None] * xs.astype(jnp.float32), last, rows

        if not self.decode_slots:
            y, _, _ = block(None)
        else:
            # has_variable is False exactly during .init(): create the
            # state, write nothing (models/gpt.py's guard)
            ready = self.has_variable("cache", "ssm_state")
            sv = self.variable("cache", "ssm_state", jnp.zeros,
                               (bsz, hn, p, n), jnp.float32)
            tv = self.variable("cache", "conv_tail", jnp.zeros,
                               (bsz, taps - 1, width), self.dtype)
            if not ready:
                y, _, _ = block(None)
            elif prompt_len is not None:
                # PREFILL: from zero, pads inert; the slot's old state is
                # not read.  Row i of ``rows`` is position i - (taps - 1).
                y, last, rows = block(jnp.arange(t)[None, :]
                                      < prompt_len[:, None])
                at = prompt_len[:, None] + jnp.arange(taps - 1)[None, :]
                sv.value = last
                tv.value = jnp.take_along_axis(
                    rows, at[..., None], axis=1).astype(tv.value.dtype)
            else:
                if t != 1:
                    raise ValueError(
                        "the state-space step takes one token a slot: a "
                        "token block cannot be scored against a recurrent "
                        "state and taken back")
                # STEP: the window is the tail and this token's row
                window = jnp.concatenate(
                    [tv.value.astype(xbc.dtype), xbc], axis=1)
                conv = jnp.einsum("bkw,kw->bw", window.astype(jnp.float32),
                                  conv_w) + conv_b
                xs, b, c = split(jax.nn.silu(conv).astype(self.dtype))
                y, new = ssd_step(sv.value, xs, dt[:, 0], a, b, c)
                y = (y + skip[:, None] * xs.astype(jnp.float32))[:, None]
                keep = active if active is not None \
                    else jnp.ones((bsz,), bool)
                sv.value = jnp.where(keep[:, None, None, None], new, sv.value)
                tv.value = jnp.where(
                    keep[:, None, None],
                    window[:, 1:].astype(tv.value.dtype), tv.value)
        # gate first, then the norm over each group's d_i / G values
        y = y.reshape(bsz, t, di) * jax.nn.silu(z.astype(jnp.float32))
        y = y.reshape(bsz, t, g, di // g)
        y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + self.eps)
        y = (y.reshape(bsz, t, di) * gain).astype(self.dtype)
        return dense(self.hidden, "out_proj")(y)


class GroupedQueryAttention(nn.Module):
    """The ``*`` mixer: ``softmax(q k^T / sqrt(d))``, causal, ``kv_heads``
    key/value heads shared by ``heads / kv_heads`` query heads each, no
    biases and no position term."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    max_len: int
    decode_slots: bool
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, pos, prefill: bool):
        bsz, t, _ = x.shape
        hq, hk, d = self.heads, self.kv_heads, self.head_dim
        scale = 1.0 / math.sqrt(d)

        def dense(size, name):
            return nn.Dense(size, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        q = dense(hq * d, "q_proj")(x).reshape(bsz, t, hq, d)
        k = dense(hk * d, "k_proj")(x).reshape(bsz, t, hk, d)
        v = dense(hk * d, "v_proj")(x).reshape(bsz, t, hk, d)
        out = dense(self.hidden, "o_proj")

        def within():
            """The block attends within itself from position 0.  Up to
            ``ATTN_UNROLLED_MAX`` positions in ``causal_attention_blocked``'s
            unrolled pieces, the keys and values repeated a query head;
            beyond it (models/jamba.py's 32,768 bucket: 576 pieces of 168 MB
            score tiles, which the v5e's compiler cannot lay out beside the
            weights) in ``window_attention_blocked``'s two nested scans with
            no window, one 512 x 512 tile alive and nothing repeated."""
            if t > ATTN_UNROLLED_MAX:
                o = window_attention_blocked(
                    q.reshape(bsz, t, hk, hq // hk, d), k, v, scale, None)
            else:
                o = causal_attention_blocked(
                    q, jnp.repeat(k, hq // hk, axis=2),
                    jnp.repeat(v, hq // hk, axis=2), scale)
            return out(o.reshape(bsz, t, hq * d))

        if not self.decode_slots:
            return within()
        ready = self.has_variable("cache", "cached_key")
        ck = self.variable("cache", "cached_key", jnp.zeros,
                           (bsz, self.max_len, hk, d), self.dtype)
        cv = self.variable("cache", "cached_value", jnp.zeros,
                           (bsz, self.max_len, hk, d), self.dtype)
        if not ready:
            return within()
        if prefill:
            # one piece from position 0; pad rows past the prompt hold
            # keys of pad tokens, invisible under the length mask
            ck.value = lax.dynamic_update_slice_in_dim(
                ck.value, k.astype(ck.value.dtype), 0, axis=1)
            cv.value = lax.dynamic_update_slice_in_dim(
                cv.value, v.astype(cv.value.dtype), 0, axis=1)
            return within()
        ck.value = write_slot_rows(ck.value, k.astype(ck.value.dtype), pos)
        cv.value = write_slot_rows(cv.value, v.astype(cv.value.dtype), pos)
        keys, values = (ck.value.astype(self.dtype),
                        cv.value.astype(self.dtype))
        qg = q.reshape(bsz, t, hk, hq // hk, d)
        s = jnp.einsum("btkgd,blkd->bkgtl", qg, keys,
                       preferred_element_type=jnp.float32) * scale
        valid = (jnp.arange(self.max_len)[None, None, :]
                 <= pos[:, :, None])                              # (b, t, l)
        prob = jax.nn.softmax(
            jnp.where(valid[:, None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("bkgtl,blkd->btkgd", prob.astype(self.dtype), values)
        return out(o.reshape(bsz, t, hq * d))


class HybridSSMLM(nn.Module):
    """Decoder-only LM of the blocks above: token ids (B, L) -> next-token
    logits (B, L, V) in float32.

    ``param_dtype`` is what the weights are held in, ``dtype`` what the
    matrix products run in; router, softmax, norms, decays and the
    recurrent state are float32."""

    vocab_size: int = 512
    hidden: int = 64
    pattern: str = "MEM*E"       # one mixer a layer: M, * or E
    ssm_heads: int = 8
    ssm_head_dim: int = 16       # ssm_heads * ssm_head_dim = expand * hidden
    ssm_groups: int = 2
    ssm_state: int = 16
    conv_kernel: int = 4
    chunk: int = 128
    dt_limits: tuple[float, float, float] = (0.001, 0.1, 1e-4)
    heads: int = 4               # query heads of the attention layers
    kv_heads: int = 2
    head_dim: int = 16
    num_experts: int = 16        # router width
    experts_per_token: int = 4
    expert_ffn: int = 24         # width of one routed expert
    expert_latent: int = 32      # what the routed experts read and write
    shared_ffn: int = 48         # the shared expert, on the hidden state
    routed_scale: float = 1.0
    norm_topk: bool = True
    experts_held: tuple[int, int] | None = None   # (first, count); None = all
    eps: float = 1e-5
    max_len: int = 512
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    decode_slots: bool = False   # serving: the batch dim is a SLOT table
                                 # (serving/kv_cache.py), positions are the
                                 # caller's

    causal_lm = True

    def slot_decode_clone(self, *, mesh=None,
                          kv_quant: bool = False) -> "HybridSSMLM":
        """The module ``SlotKVCache`` serves from: the same under any
        ``mesh`` (replicated over a 'model' axis), and with float rows
        (its contract declares no int8 rows, so the table refuses
        ``kv_quant``)."""
        return self.clone(decode=True, decode_slots=True)

    def slot_contract(self) -> SlotContract:
        """Per-slot state beside the rows (the recurrent state is never
        narrowed to the table's ``kv_dtype``, the convolution tail may
        be); a prompt cannot resume from a position: the state has no
        positions to resume at."""
        return SlotContract(
            takes_active=True,
            slot_state={"ssm_state": False, "conv_tail": True},
            expert_layers=self.pattern.count("E"),
            num_experts=self.num_experts,
            experts_per_token=self.experts_per_token,
            experts_held=self.experts_held)

    @nn.compact
    def __call__(self, token_ids, train: bool = False, positions=None,
                 prompt_len=None, active=None):
        b, t = token_ids.shape
        if set(self.pattern) - set("M*E") or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: one of M, *, E a "
                             f"layer")
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
        # pad tokens of a prefill bucket, and the stale tokens of slots
        # that sit a round out, go to no expert
        if prefill:
            routed = jnp.arange(t)[None, :] < prompt_len[:, None]
        else:
            routed = None if active is None else active[:, None]

        kinds = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        norm = lambda name: RMSNorm(self.eps, **kinds, name=name)
        x = nn.Embed(self.vocab_size, self.hidden, **kinds,
                     name="token_embed")(token_ids)
        for i, kind in enumerate(self.pattern):
            y = norm(f"norm_{i}")(x)
            if kind == "M":
                with jax.named_scope("ssm_mixer"):
                    y = MambaMixer(
                        self.hidden, self.ssm_heads, self.ssm_head_dim,
                        self.ssm_groups, self.ssm_state, self.conv_kernel,
                        self.chunk, self.dt_limits, self.eps,
                        self.decode_slots, **kinds,
                        name=f"mixer_{i}")(y, prompt_len, active)
            elif kind == "*":
                y = GroupedQueryAttention(
                    self.hidden, self.heads, self.kv_heads, self.head_dim,
                    self.max_len, self.decode_slots, **kinds,
                    name=f"mixer_{i}")(y, pos, prefill)
            else:
                y = DroplessMoE(
                    num_experts=self.num_experts,
                    top_k=self.experts_per_token, hidden=self.expert_ffn,
                    shared_hidden=self.shared_ffn,
                    routed_scale=self.routed_scale, norm_topk=self.norm_topk,
                    held=self.experts_held, expert_act="relu2",
                    latent=self.expert_latent, **kinds, name=f"mixer_{i}")(
                        y.reshape(b * t, self.hidden),
                        None if routed is None else routed.reshape(b * t)
                ).reshape(b, t, self.hidden)
            x = x + y
        if prefill:     # the one position whose logits sample a token
            x = jnp.take_along_axis(
                x, (prompt_len - 1)[:, None, None].astype(jnp.int32), axis=1)
        x = norm("final_norm")(x)
        logits = nn.Dense(self.vocab_size, use_bias=False, **kinds,
                          name="lm_head")(x)
        return logits.astype(jnp.float32)
