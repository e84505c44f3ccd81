"""Plain grouped-query decoder whose attention keeps the top keys of a
learned indexer, with softmax-routed experts (the Keye-VL-2.0 text decoder):
the full causal forward in straightforward jnp.

float32 with ``highest`` matmul precision; the index scores of a block of
queries against every key, the selection of ``lax.top_k`` (found by
counting: ``select``), attention under the selection's mask, the experts one at a time over the tokens routed to
them; no kernels, no cache, no batching; imports nothing of the program.
Each weight is raised to float32 where it is used, so a sequence of 65,536
tokens at published widths fits beside the bfloat16 weights on one chip.
``tests/keye_reference.py`` is a copy of this file (``tests/test_keye.py``
holds the two equal).

    layer i:  x = x + Attn(RMS(x)) W_o;  x = x + MoE(RMS'(x))
    logits = RMS_final(x) W_head

Attention, for token t with h = RMS(x):

    q_{t,i}  = rope(RMS_q(h W_q)_i)       k_{s,g} = rope(RMS_k(h W_k)_g)
    v_{s,g}  = (h W_v)_g
    qI_{t,j} = rope_r((h W_IQ)_j)         kI_s = rope_r(LN(h W_IK))
    w_{t,j}  = (h W_w)_j / sqrt(Hi)
    I_{t,s}  = sum_j w_{t,j} relu(qI_{t,j} . kI_s) / sqrt(Di)
    S_t      = the rows of lax.top_k of I_{t, s <= t} (every s <= t while
               t < top_k; ties to the lower position)
    o_{t,i}  = softmax over S_t of q_{t,i} . k_{s,g(i)} / sqrt(d), times v

``rope`` rotates halves of the whole head by ``pos * theta^(-2i/d)``,
``rope_r`` the first ``index_rope_dim`` dims of an indexer head the same
way; ``LN`` has a gain and a bias.  MoE: ``p = softmax(y W_r)``, the top
``experts_per_token``, weights ``p / sum`` over them, SwiGLU experts.

``weights`` (``lib/keye_weights.py`` makes them; any float dtype):

    embed (V, h), final_norm (h,), head (h, V), layers: a list of
      norm, ffn_norm (h,), q (h, Hq d), k, v (h, Hk d), o (Hq d, h),
      q_norm, k_norm (d,), index_q (h, Hi Di), index_k (h, Di),
      index_k_norm, index_k_bias (Di,), index_weights (h, Hi),
      router (h, E), w_gate, w_up (E, h, f), w_down (E, f, h)

Departures from the published model: no vision tower; the indexer's keys
are float32 here (bfloat16 in the program, FP8 after a Hadamard rotation in
the published cache).

``mode`` is the precision of every matrix product: ``"f32"`` the
reference, ``"fp8"`` both operands rounded to float8 (e4m3, one max-abs
scale per contracted vector), the control.  ``fault`` plants what a
comparison must catch (``FAULTS``)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

FAULTS = (
    "no_index_rope",    # the indexer's queries and keys not rotated
    "no_qk_norm",       # q and k without their per-head RMSNorm
    "no_norm_topk",     # the chosen experts' weights not divided by their sum
)

QUERY_BLOCK = 128       # queries of the attention at a time
FIRST_GROUP = 4096      # queries before the first doubling of the keys read


def dims_of(cfg: dict) -> dict:
    sa = cfg["sa_config"]
    return dict(q_heads=int(cfg["num_attention_heads"]),
                kv_heads=int(cfg["num_key_value_heads"]),
                head_dim=int(cfg["head_dim"]),
                index_heads=int(sa["indexer_num_heads"]),
                index_dim=int(sa["indexer_head_dim"]),
                index_rope=int(cfg["assumed"]["index_rope_dim"]),
                top_k=int(sa["topk"]),
                per_token=int(cfg["num_experts_per_tok"]),
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


def _rope(x, pos, theta: float, dims: int):
    """Rotate halves of the first ``dims`` of the last axis of ``x`` (S, H,
    D) by ``pos`` (S,)."""
    half = dims // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:dims]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., dims:]], axis=-1)


def _static(fn):
    """``fn(*arrays, dims, mode, fault)`` jitted with the last three static:
    called layer by layer outside any jit, every call of one shape shares
    one compiled program."""
    jitted = jax.jit(lambda arrays, dims, mode, fault: fn(
        *arrays, dict(dims), mode, fault), static_argnums=(1, 2, 3))

    def call(*arrays, dims, mode="f32", fault=None):
        return jitted(arrays, tuple(sorted(dims.items())), mode, fault)
    return call


def _blocked(fn, xs: tuple, block: int):
    """``fn(*xs)`` over the rows of the arrays ``xs`` (S, ...), ``block``
    at a time."""
    s = xs[0].shape[0]
    if s <= block:
        return fn(*xs)
    pad = -s % block
    xs = tuple(jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
               .reshape((-1, block) + x.shape[1:]) for x in xs)
    out = lax.map(lambda blk: fn(*blk), xs)
    return out.reshape((-1,) + out.shape[2:])[:s]


# ------------------------------------------------------------ attention

@_static
def project(x, w, dims, mode, fault):
    """``x`` (S, h) normed -> q (S, Hq, d), k, v (S, Hk, d), qI (S, Hi, Di),
    kI (S, Di), w (S, Hi)."""
    s = x.shape[0]
    hq, hk, d = dims["q_heads"], dims["kv_heads"], dims["head_dim"]
    hi, di = dims["index_heads"], dims["index_dim"]
    pos = jnp.arange(s)
    q = _mm(x, w["q"], mode, "sk,kn->sn").reshape(s, hq, d)
    k = _mm(x, w["k"], mode, "sk,kn->sn").reshape(s, hk, d)
    v = _mm(x, w["v"], mode, "sk,kn->sn").reshape(s, hk, d)
    if fault != "no_qk_norm":
        q = _rms(q, w["q_norm"], dims["eps"])
        k = _rms(k, w["k_norm"], dims["eps"])
    q, k = (_rope(t, pos, dims["theta"], d) for t in (q, k))
    qi = _mm(x, w["index_q"], mode, "sk,kn->sn").reshape(s, hi, di)
    ki = _mm(x, w["index_k"], mode, "sk,kn->sn")
    ki = ki - jnp.mean(ki, -1, keepdims=True)
    ki = ki * lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True) + dims["eps"]) \
        * w["index_k_norm"].astype(jnp.float32) \
        + w["index_k_bias"].astype(jnp.float32)
    if fault != "no_index_rope":
        qi = _rope(qi, pos, dims["theta"], dims["index_rope"])
        ki = _rope(ki[:, None], pos, dims["theta"], dims["index_rope"])[:, 0]
    wt = _mm(x, w["index_weights"], mode, "sk,kn->sn") / math.sqrt(hi)
    return q, k, v, qi, ki, wt


def index_scores(qi, wt, ki, dims, mode="f32"):
    """``I`` of queries ``qi`` (R, Hi, Di), ``wt`` (R, Hi) against keys
    ``ki`` (P, Di): (R, P) float32."""
    r = jax.nn.relu(_mm(qi, ki, mode, "qjk,sk->qjs"))
    return jnp.einsum("qjs,qj->qs", r, wt, precision=lax.Precision.HIGHEST) \
        / math.sqrt(dims["index_dim"])


def _order(x):
    """float32 -> int32 of the same order (-0.0 taken as 0.0)."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def select(scores, at, top_k: int):
    """The selection of queries at positions ``at`` (R,) as a mask (R, P)
    over ``scores`` (R, P): the rows of ``lax.top_k`` of each query's scores
    at ``s <= t`` (ties to the lower position).  Computed by counting, not
    by sorting (``tests/test_keye.py`` holds the two equal): each row's
    top_k-th largest score by bisection over the float's order, then the
    ties at it from the lowest position up, as many as the top_k still
    needs.  A ``lax.top_k`` of 2,048 over 60k keys is a sort for every
    block of queries, and minutes of the check at the cell's longest
    request."""
    p = scores.shape[1]
    valid = jnp.arange(p)[None, :] <= at[:, None]
    key = jnp.where(valid, _order(scores), jnp.iinfo(jnp.int32).min)
    k = jnp.minimum(top_k, valid.sum(1, keepdims=True))

    def halve(_, bounds):       # count(key >= lo) >= k > count(key >= hi)
        lo, hi = bounds
        mid = (lo >> 1) + (hi >> 1) + (lo & hi & 1)
        up = (key >= mid).sum(1, keepdims=True) >= k
        return jnp.where(up, mid, lo), jnp.where(up, hi, mid)

    start = jnp.full(k.shape, jnp.iinfo(jnp.int32).min, jnp.int32)
    lo, _ = lax.fori_loop(0, 32, halve, (start, jnp.full(
        k.shape, jnp.iinfo(jnp.int32).max, jnp.int32)))
    above = valid & (key > lo)
    ties = valid & (key == lo)
    need = k - above.sum(1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, axis=1) <= need))


@_static
def attend(q, qi, wt, at, k, v, ki, dims, mode, fault):
    """Queries ``q`` (R, Hq, d) with ``qi``, ``wt`` at positions ``at`` (R,)
    over the keys ``k``, ``v`` (P, Hk, d), ``ki`` (P, Di): (R, Hq, d)."""
    hk = dims["kv_heads"]
    g = dims["q_heads"] // hk

    def block(q_b, qi_b, wt_b, at_b):
        mask = select(index_scores(qi_b, wt_b, ki, dims, mode), at_b,
                      dims["top_k"])
        scores = _mm(q_b.reshape(q_b.shape[0], hk, g, -1), k, mode,
                     "qhgk,thk->hgqt") * dims["head_dim"] ** -0.5
        prob = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        return _mm(prob, v, mode, "hgqk,khd->qhgd").reshape(q_b.shape)

    return _blocked(block, (q, qi, wt, at), QUERY_BLOCK)


def _groups(s: int) -> list[tuple[int, int]]:
    """Query ranges ``[lo, hi)``, each attending the keys ``[0, hi)``: the
    first ``FIRST_GROUP`` queries, then doublings."""
    out, lo, hi = [], 0, min(s, FIRST_GROUP)
    while lo < s:
        out.append((lo, hi))
        lo, hi = hi, min(s, 2 * hi)
    return out


@_static
def _out_proj(*args):
    """The groups' outputs, joined, through ``W_o`` (one program, so that a
    65,536-row float32 output is not held twice around the product)."""
    *parts, w_o, dims, mode, fault = args
    o = jnp.concatenate(parts, 0)
    return _mm(o.reshape(o.shape[0], -1), w_o, mode, "sk,kn->sn")


def attention(x, w, dims, mode="f32", fault=None, keep=None):
    """Grouped-query causal attention over the selection, for one sequence
    ``x`` (S, h) normed; with ``keep`` (positions), also ``(qI, w)`` at
    those positions and every ``kI``."""
    q, k, v, qi, ki, wt = project(x, w, dims=dims, mode=mode, fault=fault)
    at = jnp.arange(x.shape[0])
    parts = [attend(q[lo:hi], qi[lo:hi], wt[lo:hi], at[lo:hi], k[:hi],
                    v[:hi], ki[:hi], dims=dims, mode=mode, fault=fault)
             for lo, hi in _groups(x.shape[0])]
    out = _out_proj(*parts, w["o"], dims=dims, mode=mode, fault=fault)
    if keep is None:
        return out
    return out, (qi[keep], wt[keep], ki)


# -------------------------------------------------------------- experts

def route(x, w, dims, mode="f32", fault=None):
    """``(S, E)`` float32: each token's weight on each expert, 0 where the
    expert is not among its chosen."""
    p = jax.nn.softmax(_mm(x, w["router"], mode, "sk,kn->sn"), -1)
    top, chosen = lax.top_k(p, dims["per_token"])
    if dims["norm_topk"] and fault != "no_norm_topk":
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(p).at[jnp.arange(x.shape[0])[:, None],
                                chosen].set(top)


def choice_margin(x, w, dims):
    """``(S,)``: how far each token's last chosen expert lies above its
    first unchosen one, in softmax probability.  Where that is less than
    rounding moves a score, a lower precision chooses another expert and a
    logit moves by a step: a comparison may set such positions apart."""
    p = jax.nn.softmax(_mm(x, w["router"], "f32", "sk,kn->sn"), -1)
    top, _ = lax.top_k(p, dims["per_token"] + 1)
    return top[:, -2] - top[:, -1]


@_static
def _expert_rows(x, weight, order, start, w, dims, mode, fault):
    """Every expert in turn over the rows routed to it: ``order`` (S * E)
    lists the rows expert by expert, expert ``e``'s from ``start[e]`` to
    ``start[e + 1]``; a static ``cap`` of them is read for each (the
    length of the scan's window), the ones past its count under a mask."""
    s, e = weight.shape
    cap = dims["cap"]
    padded = jnp.concatenate([order, jnp.zeros(cap, order.dtype)])

    def one(y, inp):
        gate, up, down, i = inp
        rows = lax.dynamic_slice_in_dim(padded, start[i], cap)
        mine = jnp.arange(cap) < start[i + 1] - start[i]
        xs = x[rows]
        h = jax.nn.silu(_mm(xs, gate, mode, "sk,kn->sn")) \
            * _mm(xs, up, mode, "sk,kn->sn")
        out = _mm(h, down, mode, "sk,kn->sn") \
            * jnp.where(mine, weight[rows, i], 0.0)[:, None]
        return y.at[rows].add(out), None

    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (w["w_gate"], w["w_up"], w["w_down"], jnp.arange(e)))
    return y


def experts(x, w, dims, mode="f32", fault=None):
    """The expert layer over ``x`` (S, h): the rows each expert was chosen
    by, gathered, through that expert, weighted and added back."""
    weight = _route(x, w, dims=dims, mode=mode, fault=fault)
    chosen = np.asarray(weight != 0)
    counts = chosen.sum(0)
    order = np.argsort(~chosen.T.reshape(-1), kind="stable")
    order = (order % x.shape[0]).astype(np.int32)
    cap = 1 << max(int(counts.max()) - 1, 1).bit_length()
    start = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return _expert_rows(x, weight, jnp.asarray(order), jnp.asarray(start), w,
                        dims={**dims, "cap": cap}, mode=mode, fault=fault)


@_static
def _route(x, w, dims, mode, fault):
    return route(x, w, dims, mode, fault)


@_static
def _margin(x, w, dims, mode, fault):
    return choice_margin(x, w, dims)


@_static
def _norm_in(x, g, dims, mode, fault):
    return _rms(x, g, dims["eps"])


# ------------------------------------------------------------- the model

def hidden_fn(weights: dict, tokens, dims: dict, *, mode: str = "f32",
              fault: str | None = None, margins: bool = False, keep=None):
    """``(S,)`` token ids -> ``(S, h)`` float32 after the final norm; with
    ``margins`` also ``(S,)``, the least ``choice_margin`` of each position
    over the layers; with ``keep`` (positions), also each layer's ``(qI, w)``
    at those positions and its ``kI`` (``attention``)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    x = weights["embed"][tokens].astype(jnp.float32)
    least = jnp.full(tokens.shape, jnp.inf, jnp.float32)
    kept = []
    for w in weights["layers"]:
        y = attention(_norm_in(x, w["norm"], dims=dims), w, dims, mode, fault,
                      keep)
        if keep is not None:
            y, index = y
            kept.append(index)
        x = x + y
        y = _norm_in(x, w["ffn_norm"], dims=dims)
        if margins:
            least = jnp.minimum(least, _margin(y, w, dims=dims))
        x = x + experts(y, w, dims, mode, fault)
    x = _norm_in(x, weights["final_norm"], dims=dims)
    out = (x,) + ((least,) if margins else ()) + ((kept,) if keep is not None
                                                  else ())
    return out if len(out) > 1 else x


def head_fn(weights: dict, hidden, *, mode: str = "f32"):
    """``(n, h)`` normed hidden rows -> ``(n, V)`` float32 logits."""
    return _mm(hidden, weights["head"], mode, "sk,kv->sv")


def logits_fn(weights: dict, tokens, dims: dict, *, mode: str = "f32",
              fault: str | None = None):
    """``(S,)`` token ids -> ``(S, V)`` float32 next-token logits."""
    return head_fn(weights, hidden_fn(weights, tokens, dims, mode=mode,
                                      fault=fault), mode=mode)
