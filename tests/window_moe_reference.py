"""Plain window-and-global-attention, sparse-expert decoder with a parallel
block (the ``cohere2_moe`` block family): the full causal forward in
straightforward jnp.

float32 with ``highest`` matmul precision; attention over the whole
sequence under the window mask (no cache, no ring, no skipped blocks), the
held experts one at a time under a mask, the shared experts one at a time
and averaged, no kernels, no batching; imports nothing of the program.
Each weight is raised to float32 where it is used, and attention takes one
key/value head and ``ROWS`` queries at a time, so that a sequence of
thirteen thousand tokens at published widths fits beside the bfloat16
weights on one chip.  ``tests/window_moe_reference.py`` is a copy of this
file (``tests/test_window_moe.py`` holds the two equal).

A layer, with ``n = LN_i(x)`` (mean taken out, divided by ``sqrt(var +
eps)``, times a gain, no offset): ``x' = x + Attn_i(n) + FFN_i(n)``; a
final LN, then ``logits = logit_scale * x E^T`` with ``E`` the embedding.
``weights`` (``lib/window_moe_weights.py`` makes them; any float dtype):

    embed (V, h), final_norm (h,), layers: a list of
      norm (h,), q (h, H_q d), k, v (h, H_kv d), o (H_q d, h),
      router (h, E), w_gate, w_up (n, h, m), w_down (n, m, h),
      shared_gate, shared_up (h, S m), shared_down (S m, h)

with ``n`` the experts held (all ``E``, or ``dims["held"] = (first,
count)``: the three stacks then hold either all ``E`` experts or just those
``count``) and shared expert ``j`` of ``S`` the columns (rows, for
``shared_down``) ``[j m, (j + 1) m)``.  ``dims`` (``dims_of`` reads them
off a ``config.json`` of the family): q_heads, kv_heads, head_dim, window,
theta, windowed (a bool a layer), top_k, norm_topk, shared, logit_scale,
eps, held.

``Attn_i``: ``q = n W_q``, ``k = n W_k``, ``v = n W_v``, no biases, no norm
on q or k; query head ``h`` reads key/value head ``h // (H_q / H_kv)``;
scores ``q k^T / sqrt(d)``, softmax, ``W_o``.  In a WINDOW layer q and k
are rotated by position on adjacent pairs ``(2j, 2j + 1)`` with ``theta_j =
theta^(-2j / d)`` and query ``t`` sees ``t - window < s <= t``; in a FULL
layer there is no position term and query ``t`` sees ``s <= t``.
``FFN(n) = sum_{e in top k} w_e E_e(n) + (1/S) sum_j S_j(n)``: ``s =
sigmoid(n W_g)``, the ``top_k`` largest chosen, ``w_e = s_e`` over the sum
of the chosen (``norm_topk``); ``E_e(u) = W_down(silu(W_gate u) * (W_up
u))`` and ``S_j`` the same form.  A chosen expert that is not held adds
nothing.

Departures from the published description: everything is float32 where the
family runs bfloat16 with a float32 router and softmax; the vision tower is
left out.

``mode`` is the precision of every matrix product, as in
``lib/reference.py``: ``"f32"`` the reference, ``"fp8"`` both operands
rounded to float8 (e4m3, one max-abs scale per contracted vector), the
control.  ``fault`` plants what a comparison must catch (``FAULTS``); the
two that a prefill bucket can commit take the prompt's length and how many
pad rows the bucket had (``prompt_len``, ``pads``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

ROWS = 512      # queries of one key/value head scored at a time

FAULTS = (
    "window_off",        # the window layers see every key s <= t
    "window_one_wider",  # ... see t - window <= s: one key too many
    "rope_in_full",      # the full layers rotate q and k too
    "rope_half_split",   # pairs (j, j + d/2) rotated, not (2j, 2j + 1)
    "ring_row_rotation",  # a key rotated by its ROW in the ring (position
                         # mod window), the query by its position
    "pads_in_ring",      # the bucket's pad rows written into the ring: for
                         # the served tokens, position s holds the pad key
                         # of position s + window where the bucket reaches it
    "ring_kept",         # every ring row taken as valid: a served query at
                         # t < window - 1 also sees rows t + 1 .. window - 1
                         # as the last occupant left them (ANOTHER sequence:
                         # this one's ids plus one)
    "shared_summed",     # the shared experts summed, not averaged
    "sequential_block",  # FFN reads LN(x + Attn(n)), not n
    "no_gain",           # every norm's gain left out
)


def dims_of(cfg: dict) -> dict:
    held = cfg.get("experts_held")
    return dict(q_heads=int(cfg["num_attention_heads"]),
                kv_heads=int(cfg["num_key_value_heads"]),
                head_dim=int(cfg["head_dim"]),
                window=int(cfg["sliding_window"]),
                theta=float(cfg["rope_theta"]),
                windowed=tuple(t == "sliding_attention"
                               for t in cfg["layer_types"]),
                top_k=int(cfg["num_experts_per_tok"]),
                norm_topk=bool(cfg["norm_topk_prob"]),
                shared=int(cfg["num_shared_experts"]),
                logit_scale=float(cfg["logit_scale"]),
                eps=float(cfg["layer_norm_eps"]),
                held=None if held is None else (int(held[0]), int(held[1])))


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


def _ln(x, g, eps, fault=None):
    x = x - jnp.mean(x, -1, keepdims=True)
    y = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y if fault == "no_gain" else y * g.astype(jnp.float32)


def _swiglu(x, gate, up, down, mode):
    h = jax.nn.silu(_mm(x, gate, mode, "sk,kn->sn")) \
        * _mm(x, up, mode, "sk,kn->sn")
    return _mm(h, down, mode, "sk,kn->sn")


# ------------------------------------------------------------- attention

def rotate(x, pos, theta: float, half_split: bool = False):
    """``x`` (S, H, d) rotated by ``pos`` (S,): the pairs ``(2j, 2j + 1)``
    by ``pos * theta^(-2j / d)``; with ``half_split`` the pairs ``(j, j +
    d / 2)`` (a planted fault)."""
    d2 = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] \
        * theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)     # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if half_split:
        a, b = x[..., :d2], x[..., d2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _softmax_rows(q, k, v, mask_of, mode):
    """``softmax(q k^T) v`` a key/value head and ``ROWS`` queries at a
    time.  ``q`` (S, Hk, G, d) already scaled; ``k``, ``v`` (T, Hk, d);
    ``mask_of(t_pos)`` gives the (rows, T) mask of the queries at
    ``t_pos``.  Returns (S, Hk, G, d)."""
    s, hk, g, d = q.shape
    rows = min(ROWS, s)
    pad = -s % rows
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    blocks = q.reshape((s + pad) // rows, rows, hk, g, d)

    def one_head(inp):
        qh, kh, vh = inp                # (n, rows, G, d), (T, d), (T, d)

        def one_block(inp):
            qb, first = inp
            scores = _mm(qb, kh, mode, "qgk,tk->gqt")
            mask = mask_of(first + jnp.arange(rows))
            prob = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf),
                                  axis=-1)
            return _mm(prob, vh.T, mode, "gqk,dk->qgd")

        return lax.map(one_block, (qh, jnp.arange(qh.shape[0]) * rows))

    out = lax.map(one_head, (jnp.moveaxis(blocks, 2, 0),
                             jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    # (Hk, n, rows, G, d) -> (S, Hk, G, d)
    return jnp.moveaxis(out, 0, 2).reshape(s + pad, hk, g, d)[:s]


def attention(x, w, dims, windowed: bool, mode="f32", fault=None,
              prompt_len=None, pads=0, other=None):
    """One layer's attention over one sequence ``x`` (S, h); also its keys
    and values as a table would hold them ``(k, v)`` (rotated in a window
    layer).  ``other`` is such a pair of ANOTHER sequence (``ring_kept``),
    or of this prompt followed by its bucket's pads (``pads_in_ring``)."""
    s = x.shape[0]
    hq, hk, d, window = (dims[key] for key in ("q_heads", "kv_heads",
                                               "head_dim", "window"))
    q = _mm(x, w["q"], mode, "sk,kn->sn").reshape(s, hq, d)
    k = _mm(x, w["k"], mode, "sk,kn->sn").reshape(s, hk, d)
    v = _mm(x, w["v"], mode, "sk,kn->sn").reshape(s, hk, d)
    at = jnp.arange(s)
    if windowed or fault == "rope_in_full":
        half = fault == "rope_half_split"
        q = rotate(q, at, dims["theta"], half)
        k = rotate(k, at % window if fault == "ring_row_rotation" else at,
                   dims["theta"], half)
    q = q.reshape(s, hk, hq // hk, d) * d ** -0.5
    if windowed and fault != "window_off":
        reach = window + 1 if fault == "window_one_wider" else window
        mask_of = lambda t: (at[None, :] <= t[:, None]) \
            & (at[None, :] > t[:, None] - reach)
    else:
        mask_of = lambda t: at[None, :] <= t[:, None]
    o = _softmax_rows(q, k, v, mask_of, mode)
    if windowed and fault in ("pads_in_ring", "ring_kept") \
            and other is not None:
        k2, v2 = other
        if fault == "pads_in_ring":
            # position s holds the pad of position s + window wherever the
            # bucket (prompt_len + pads rows) reaches that position
            late = at + window
            swap = (late >= prompt_len) & (late < prompt_len + pads) \
                & (late < s)
            src = jnp.clip(late, 0, s - 1)
            k2, v2 = (jnp.where(swap[:, None, None], t2[src], t)
                      for t2, t in ((k2, k), (v2, v)))
            o2 = _softmax_rows(q, k2, v2, mask_of, mode)
        else:
            # rows past the query's own, up to the ring's end, as another
            # sequence left them, beside the query's own keys
            stale = lambda t: (at[None, :] > t[:, None]) \
                & (at[None, :] < window)
            both = lambda t: jnp.concatenate([mask_of(t), stale(t)], 1)
            o2 = _softmax_rows(q, jnp.concatenate([k, k2]),
                               jnp.concatenate([v, v2]), both, mode)
        # the prefill itself attends within the block: only the served
        # tokens read the table
        o = jnp.where((at >= prompt_len)[:, None, None, None], o2, o)
    return _mm(o.reshape(s, hq * d), w["o"], mode, "sk,kn->sn"), (k, v)


# --------------------------------------------------------------- experts

def choice_margin(x, w, dims):
    """``(S,)``: how far each token's last chosen expert lies above its
    first unchosen one.  Where that is less than rounding moves a score, a
    lower precision chooses another expert and a logit moves by a step: a
    comparison may set such positions apart."""
    scores = jax.nn.sigmoid(_mm(x, w["router"], "f32", "sk,kn->sn"))
    top, _ = lax.top_k(scores, dims["top_k"] + 1)
    return top[:, -2] - top[:, -1]


def route(x, w, dims, mode="f32"):
    """``(S, E)`` float32: each token's weight on each expert, 0 where the
    expert is not among its chosen."""
    scores = jax.nn.sigmoid(_mm(x, w["router"], mode, "sk,kn->sn"))
    _, chosen = lax.top_k(scores, dims["top_k"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    if dims["norm_topk"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(picked)


def experts(x, w, dims, mode="f32", fault=None, held=None):
    """The expert layer over ``x`` (S, h): every held expert in turn over
    all the tokens, weighted by ``route`` (0 for a token that did not
    choose it), plus the mean of the shared experts, one at a time.
    ``held = (first, count)`` keeps the routed part to those experts' share
    (the router still scores all)."""
    weight = route(x, w, dims, mode)
    first, count = held or dims.get("held") or (0, weight.shape[1])
    mine = slice(first, first + count)
    stored = slice(None) if w["w_up"].shape[0] == count else mine

    def one(y, ew):
        gate, up, down, col = ew
        return y + col[:, None] * _swiglu(x, gate, up, down, mode), None

    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (w["w_gate"][stored], w["w_up"][stored],
                     w["w_down"][stored], weight.T[mine]))
    n, m = dims["shared"], w["shared_gate"].shape[1] // dims["shared"]
    together = 0.0
    for j in range(n):
        cols = slice(j * m, (j + 1) * m)
        together = together + _swiglu(
            x, w["shared_gate"][:, cols], w["shared_up"][:, cols],
            w["shared_down"][cols], mode)
    return y + (together if fault == "shared_summed" else together / n)


# ------------------------------------------------------------- the model

def hidden_fn(weights: dict, tokens, dims: dict, *, mode: str = "f32",
              fault: str | None = None, margins: bool = False,
              prompt_len=None, pads=0):
    """``(S,)`` token ids -> ``(S, h)`` float32 after the final norm; with
    ``margins`` also ``(S,)``, the least ``choice_margin`` of each position
    over the layers."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    if fault in ("pads_in_ring", "ring_kept") and prompt_len is None:
        raise ValueError(f"{fault} is planted at a prompt_len")
    eps = dims["eps"]

    def forward(ids, others, fault):
        """The layers over ``ids``; ``others[i]`` is layer ``i``'s
        ``other``.  Returns the hidden rows, the least margins and every
        layer's keys and values."""
        x = weights["embed"][ids].astype(jnp.float32)
        least = jnp.full(ids.shape, jnp.inf, jnp.float32)
        tables = []
        for i, (w, windowed) in enumerate(zip(weights["layers"],
                                              dims["windowed"])):
            n = _ln(x, w["norm"], eps, fault)
            a, table = attention(n, w, dims, windowed, mode, fault,
                                 prompt_len, pads,
                                 others[i] if others else None)
            tables.append(table)
            if fault == "sequential_block":
                n = _ln(x + a, w["norm"], eps, fault)
            if margins:
                least = jnp.minimum(least, choice_margin(n, w, dims))
            x = x + a + experts(n, w, dims, mode, fault)
        return _ln(x, weights["final_norm"], eps, fault), least, tables

    others = None
    if fault == "ring_kept":
        _, _, others = forward((tokens + 1) % weights["embed"].shape[0],
                               None, None)
    if fault == "pads_in_ring":
        # what the block prefill computed at the pad rows: the prompt, then
        # tokens of id 0
        _, _, others = forward(
            jnp.where(jnp.arange(tokens.shape[0]) < prompt_len, tokens, 0),
            None, None)
    x, least, _ = forward(tokens, others, fault)
    return (x, least) if margins else x


def head_fn(weights: dict, hidden, dims: dict, *, mode: str = "f32"):
    """``(n, h)`` normed hidden rows -> ``(n, V)`` float32 logits of the
    tied head."""
    return dims["logit_scale"] * _mm(hidden, weights["embed"], mode,
                                     "sk,vk->sv")


def logits_fn(weights: dict, tokens, dims: dict, *, mode: str = "f32",
              fault: str | None = None, **kw):
    """``(S,)`` token ids -> ``(S, V)`` float32 next-token logits."""
    return head_fn(weights, hidden_fn(weights, tokens, dims, mode=mode,
                                      fault=fault, **kw), dims, mode=mode)
