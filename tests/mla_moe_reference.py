"""Plain latent-attention, sparse-expert decoder (the DeepSeek-V3 block
family): the full causal forward in straightforward jnp.

float32 with ``highest`` matmul precision, expanded attention only, experts
as a loop over all of them with a mask, no cache, no kernels, no batching;
imports nothing of the program.  Attention goes in query blocks and the
experts one at a time, each weight raised to float32 where it is used, so
an 8k-token sequence at published widths fits beside the bfloat16 weights
on one chip.  ``tests/mla_moe_reference.py`` is a copy of this file
(``tests/test_mla_moe.py`` holds the two equal).

``weights`` (``lib/mla_moe_weights.py`` makes them; any float dtype):

    embed (V, h), head (h, V), final_norm (h,), layers: a list of
      attn_norm (h,), q (h, H (d_n + d_r)), kv_a (h, r + d_r),
      kv_a_norm (r,), kv_b (r, H (d_n + d_v)), o (H d_v, h), ffn_norm (h,)
      and either  gate, up (h, f), down (f, h)                 (dense MLP)
      or  router (h, E), choice_bias (E,), w_gate, w_up (E, h, m),
          w_down (E, m, h), shared_gate, shared_up (h, s), shared_down (s, h)

``dims`` (``dims_of`` reads them off a ``config.json`` of the family):
heads, d_n, d_r, d_v, rank, top_k, routed_scale, norm_topk, theta, eps.

Departures from the published code (transformers' ``modeling_deepseek_v3``):
RoPE rotates the adjacent pairs ``(2i, 2i+1)`` in place where the
published code first permutes them to a half-split layout
(``rope_interleave``): q_r and k_r get the same permutation, so every score
is the same number.  ``n_group`` 1 and ``topk_group`` 1 make the grouped
choice a plain top-k, which is what is written.  Everything is float32
where the published code runs bfloat16 with a float32 router and softmax.

``mode`` is the precision of every matrix product, as in
``lib/reference.py``: ``"f32"`` the reference, ``"fp8"`` both operands
rounded to float8 (e4m3, one max-abs scale per contracted vector), the
control.  ``fault`` plants what a comparison must catch (``FAULTS``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 512

FAULTS = (
    "no_shared",        # the shared expert left out
    "top_k_less_one",   # 5 experts, not 6
    "not_normalised",   # the chosen weights not divided by their sum
    "no_routed_scale",  # the factor 2.448 left out
    "bias_in_weights",  # b added to the weights, not only to the choice
    "bias_ignored",     # the choice made without b
    "k_rope_unrotated",  # k_r not rotated
    "no_kv_norm",       # RMS_kv left out
    "scale_nope_only",  # 1/sqrt(d_n) in place of 1/sqrt(d_n + d_r)
    "k_rope_wrong_columns",  # k_r read from the first d_r columns of W_kva
)


def dims_of(cfg: dict) -> dict:
    return dict(heads=int(cfg["num_attention_heads"]),
                d_n=int(cfg["qk_nope_head_dim"]),
                d_r=int(cfg["qk_rope_head_dim"]),
                d_v=int(cfg["v_head_dim"]), rank=int(cfg["kv_lora_rank"]),
                top_k=int(cfg["num_experts_per_tok"]),
                routed_scale=float(cfg["routed_scaling_factor"]),
                norm_topk=bool(cfg["norm_topk_prob"]),
                theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]))


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0   # e4m3 max
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, mode: str, eq: str):
    """``einsum(eq, a, b)`` in float32; the contracted axis is a's last and
    is named ``k`` in ``eq`` for both operands."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        a = _fp8(a, -1)
        b = _fp8(b, eq.split(",")[1].split("->")[0].index("k"))
    elif mode != "f32":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.einsum(eq, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, theta):
    """``x``: (S, ..., d), position = row; adjacent pairs rotated."""
    s, d2 = x.shape[0], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv        # (S, d/2)
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d2,))
    pairs = x.reshape(x.shape[:-1] + (d2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _swiglu(x, gate, up, down, mode):
    h = jax.nn.silu(_mm(x, gate, mode, "sk,kn->sn")) \
        * _mm(x, up, mode, "sk,kn->sn")
    return _mm(h, down, mode, "sk,kn->sn")


def attention(x, w, dims, mode="f32", fault=None):
    """Expanded latent attention over one sequence ``x`` (S, h)."""
    s = x.shape[0]
    hn, dn, dr, dv, r = (dims[k] for k in ("heads", "d_n", "d_r", "d_v",
                                           "rank"))
    q = _mm(x, w["q"], mode, "sk,kn->sn").reshape(s, hn, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], dims["theta"])], -1)
    kva = _mm(x, w["kv_a"], mode, "sk,kn->sn")
    c = kva[:, :r]
    if fault != "no_kv_norm":
        c = _rms(c, w["kv_a_norm"], dims["eps"])
    k_r = kva[:, :dr] if fault == "k_rope_wrong_columns" else kva[:, r:]
    if fault != "k_rope_unrotated":
        k_r = _rope(k_r, dims["theta"])
    kv = _mm(c, w["kv_b"], mode, "sk,kn->sn").reshape(s, hn, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None, :], (s, hn, dr))], -1)
    v = kv[..., dn:]
    scale = (dn if fault == "scale_nope_only" else dn + dr) ** -0.5
    # one block of queries at a time against all the keys, under the mask
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 2, 0)

    def one(lo):
        qb = lax.dynamic_slice_in_dim(q, lo, block, 0).transpose(1, 0, 2)
        scores = _mm(qb, kt, mode, "hqk,htk->hqt") * scale
        mask = jnp.arange(s)[None, :] <= lo + jnp.arange(block)[:, None]
        p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return _mm(p, vt, mode, "hqk,hdk->qhd")

    o = lax.map(one, jnp.arange(0, s, block)).reshape(s, hn * dv)
    return _mm(o, w["o"], mode, "sk,kn->sn")


def choice_margin(x, w, dims):
    """``(S,)``: how far each token's last chosen expert lies above its
    first unchosen one, in ``s + b``.  Where that is less than rounding
    moves a score, a lower precision chooses another expert and a logit
    moves by a step: a comparison may set such positions apart."""
    scores = jax.nn.sigmoid(_mm(x, w["router"], "f32", "sk,kn->sn"))
    top, _ = lax.top_k(scores + w["choice_bias"].astype(jnp.float32),
                       dims["top_k"] + 1)
    return top[:, -2] - top[:, -1]


def route(x, w, dims, mode="f32", fault=None):
    """``(S, E)`` float32: each token's weight on each expert, 0 where the
    expert is not among its chosen."""
    scores = jax.nn.sigmoid(_mm(x, w["router"], mode, "sk,kn->sn"))
    bias = w["choice_bias"].astype(jnp.float32)
    k = dims["top_k"] - (fault == "top_k_less_one")
    _, chosen = lax.top_k(scores if fault == "bias_ignored" else scores + bias,
                          k)
    picked = jnp.take_along_axis(
        scores + bias if fault == "bias_in_weights" else scores, chosen, -1)
    if dims["norm_topk"] and fault != "not_normalised":
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    if fault != "no_routed_scale":
        picked = picked * dims["routed_scale"]
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(picked)


def experts(x, w, dims, mode="f32", fault=None, held=None):
    """The expert layer over ``x`` (S, h): every expert in turn over all
    the tokens, weighted by ``route`` (0 for a token that did not choose
    it), plus the shared expert.  ``held = (first, count)`` keeps the
    routed part to those experts' share (the router still scores all)."""
    weight = route(x, w, dims, mode, fault)
    first, count = held or (0, weight.shape[1])

    def one(y, ew):
        gate, up, down, col = ew
        return y + col[:, None] * _swiglu(x, gate, up, down, mode), None

    mine = slice(first, first + count)
    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (w["w_gate"][mine], w["w_up"][mine], w["w_down"][mine],
                     weight.T[mine]))
    if fault != "no_shared":
        y = y + _swiglu(x, w["shared_gate"], w["shared_up"],
                        w["shared_down"], mode)
    return y


def hidden_fn(weights: dict, tokens, dims: dict, *, mode: str = "f32",
              fault: str | None = None, margins: bool = False):
    """``(S,)`` token ids -> ``(S, h)`` float32 after the final norm; with
    ``margins`` also ``(S,)``, the least ``choice_margin`` of each position
    over the expert layers."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    x = weights["embed"][tokens].astype(jnp.float32)
    least = jnp.full(tokens.shape, jnp.inf, jnp.float32)
    for w in weights["layers"]:
        x = x + attention(_rms(x, w["attn_norm"], dims["eps"]), w, dims,
                          mode, fault)
        y = _rms(x, w["ffn_norm"], dims["eps"])
        if "router" in w and margins:
            least = jnp.minimum(least, choice_margin(y, w, dims))
        x = x + (experts(y, w, dims, mode, fault) if "router" in w
                 else _swiglu(y, w["gate"], w["up"], w["down"], mode))
    x = _rms(x, weights["final_norm"], dims["eps"])
    return (x, least) if margins else x


def head_fn(weights: dict, hidden, *, mode: str = "f32"):
    """``(n, h)`` normed hidden rows -> ``(n, V)`` float32 logits."""
    return _mm(hidden, weights["head"], mode, "sk,kv->sv")


def logits_fn(weights: dict, tokens, dims: dict, *, mode: str = "f32",
              fault: str | None = None):
    """``(S,)`` token ids -> ``(S, V)`` float32 next-token logits."""
    return head_fn(weights, hidden_fn(weights, tokens, dims, mode=mode,
                                      fault=fault), mode=mode)
