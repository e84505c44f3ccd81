"""Plain hybrid state-space / attention / latent-expert decoder (the
``nemotron_h`` block family): the full causal forward in straightforward
jnp.

float32 with ``highest`` matmul precision; the state-space recurrence as a
plain ``lax.scan`` of its one-token update over the sequence (no chunks),
attention over the whole sequence under a mask (no cache), the held experts
one at a time under a mask, no kernels, no batching; imports nothing of the
program.  Each weight is raised to float32 where it is used, so a sequence
of a few thousand tokens at published widths fits beside the bfloat16
weights on one chip.  ``tests/hybrid_ssm_reference.py`` is a copy of this
file (``tests/test_hybrid_ssm.py`` holds the two equal).

A block is ONE mixer: ``x = x + Mixer_i(RMS_i(x))``; a final RMS, then
``h -> vocab``.  ``weights`` (``lib/hybrid_ssm_weights.py`` makes them; any
float dtype):

    embed (V, h), head (h, V), final_norm (h,), layers: a list of
      norm (h,) and one of
      M  in_proj (h, 2 d_i + 2 G N + H)  columns [z | xBC | dt],
         conv_w (k, d_i + 2 G N), conv_b (d_i + 2 G N,), dt_bias (H,),
         a_log (H,), d (H,), gate_norm (d_i,), out_proj (d_i, h)
      *  q (h, H_q d), k, v (h, H_kv d), o (H_q d, h)
      E  router (h, E), choice_bias (E,), latent_down (h, l),
         latent_up (l, h), w_up (n, l, m), w_down (n, m, l),
         shared_up (h, s), shared_down (s, h)

with ``d_i = H P`` and ``n`` the experts held (all ``E``, or ``dims["held"]
= (first, count)``: ``w_up`` and ``w_down`` then hold either all ``E``
experts or just those ``count``).  ``dims`` (``dims_of`` reads them off a
``config.json`` of the family): ssm_heads H, ssm_head_dim P, groups G,
state N, q_heads, kv_heads, head_dim, top_k, routed_scale, norm_topk, eps,
held.

M, per head ``h`` of group ``g = h // (H / G)``, from ``S_0 = 0``:
``xBC = silu(conv(xBC) + b)`` (causal, depthwise, k taps), ``dt =
softplus(dt + dt_bias)``, ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T``,
``y_t = S_t C_t + D_h x_t``, ``y = GroupRMS(y silu(z))`` (gate first), then
``W_out``.  ``*``: grouped-query causal softmax attention at ``1/sqrt(d)``
with NO position term (no fault plants one: on random weights the one
attention layer of eleven weighs its keys nearly alike, a rotary embedding
on q and k moved the served token's logit by 0.10 on the v5e where the
program itself reads 0.07-0.15, so this comparison cannot see it; PERF.md
section 2).  E: ``s = sigmoid(x W_g)``; the ``top_k`` largest of
``s + b``; weights ``s`` over their sum times ``routed_scale``; the experts
``W2 relu(W1 u)^2`` on ``u = x W_dn``, their weighted sum through ``W_up``,
plus the shared ``W2_s relu(W1_s x)^2`` on ``x`` itself.

Departures from the published code (transformers' ``modeling_nemotron_h``):
everything is float32 where it runs bfloat16 with a float32 router, state
and softmax; the recurrence is the one-token update where it uses a
chunked scan (the same function); ``n_group`` 1 and ``topk_group`` 1 make
the grouped choice a plain top-k, which is what is written.

``mode`` is the precision of every matrix product, as in
``lib/reference.py``: ``"f32"`` the reference, ``"fp8"`` both operands
rounded to float8 (e4m3, one max-abs scale per contracted vector), the
control.  ``fault`` plants what a comparison must catch (``FAULTS``); the
three that a slot table can commit take the prompt's length and, for the
pads, how many pad rows the prefill bucket had (``prompt_len``, ``pads``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

FAULTS = (
    "state_kept",        # a slot's state and tail not reset at admission:
                         # every state-space layer starts from where a
                         # forward over ANOTHER sequence (this one's ids
                         # plus one: the previous occupant) left it
    "pads_advance",      # the bucket's pad rows move the state: after the
                         # prompt's last token each state-space layer goes
                         # on from where a plain forward over the prompt and
                         # ``pads`` further tokens (id 0) leaves it
    "tail_at_bucket_end",  # the convolution tail taken at the bucket's end:
                         # the first served tokens see three rows that are
                         # not the prompt's last (the prompt's first three)
    "no_d_skip",         # D_h x_t left out
    "no_dt_bias",        # dt_bias left out
    "gate_after_norm",   # GroupRMS(y) silu(z) in place of GroupRMS(y silu(z))
    "relu_not_squared",  # relu(W1 u) in place of its square, both experts
    "no_routed_scale",   # the factor 5 left out
    "no_shared",         # the shared expert left out
)


def dims_of(cfg: dict) -> dict:
    held = cfg.get("experts_held")
    return dict(ssm_heads=int(cfg["mamba_num_heads"]),
                ssm_head_dim=int(cfg["mamba_head_dim"]),
                groups=int(cfg["n_groups"]), state=int(cfg["ssm_state_size"]),
                q_heads=int(cfg["num_attention_heads"]),
                kv_heads=int(cfg["num_key_value_heads"]),
                head_dim=int(cfg["head_dim"]),
                top_k=int(cfg["num_experts_per_tok"]),
                routed_scale=float(cfg["routed_scaling_factor"]),
                norm_topk=bool(cfg["norm_topk_prob"]),
                eps=float(cfg["norm_eps"]),
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


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _relu2_mlp(x, up, down, mode, fault):
    h = jax.nn.relu(_mm(x, up, mode, "sk,kn->sn"))
    if fault != "relu_not_squared":
        h = h * h
    return _mm(h, down, mode, "sk,kn->sn")


# ------------------------------------------------------- M: state space

def ssm_scan(x, dt, a, b, c, state=None, swap=None):
    """The recurrence, one token at a time.  ``x`` (S, H, P), ``dt`` (S, H)
    after the softplus, ``a`` (H,) negative, ``b``, ``c`` (S, G, N); head
    ``h`` reads group ``h // (H / G)``.  Returns ``S_t C_t`` (S, H, P) and
    the last state (H, P, N).  ``swap = (at (S,) bool, value)``: after the
    position where ``at`` is set the state becomes ``value`` (a planted
    fault)."""
    heads, groups = x.shape[1], b.shape[1]
    if state is None:
        state = jnp.zeros((heads, x.shape[2], b.shape[2]), jnp.float32)
    at, value = swap if swap is not None \
        else (jnp.zeros(x.shape[0], bool), state)
    rep = lambda t: jnp.repeat(t, heads // groups, axis=0)       # (H, N)

    def step(s, inp):
        x_t, dt_t, b_t, c_t, swap_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * rep(b_t)[:, None, :]
        y = jnp.einsum("hpn,hn->hp", s, rep(c_t),
                       precision=lax.Precision.HIGHEST)
        return jnp.where(swap_t, value, s), y

    state, y = lax.scan(step, state, (x, dt, b, c, at))
    return y, state


def mamba(x, w, dims, mode="f32", fault=None, prompt_len=None, still_from=None,
          swap=None, start=None):
    """The state-space mixer over one sequence ``x`` (S, h); also where it
    ends: ``(last state, last taps - 1 pre-activation rows)``.  From
    position ``still_from`` on ``dt`` is 0 (the state stands still);
    ``swap`` as in ``ssm_scan``; ``start`` is such an end to begin from in
    place of zeros."""
    s = x.shape[0]
    hn, p, g, n = (dims[k] for k in ("ssm_heads", "ssm_head_dim", "groups",
                                     "state"))
    di, taps = hn * p, w["conv_w"].shape[0]
    proj = _mm(x, w["in_proj"], mode, "sk,kn->sn")
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * g * n], \
        proj[:, 2 * di + 2 * g * n:]
    if fault != "no_dt_bias":
        dt = dt + w["dt_bias"].astype(jnp.float32)
    dt = jax.nn.softplus(dt)
    if still_from is not None:
        dt = jnp.where(jnp.arange(s)[:, None] < still_from, dt, 0.0)
    a = -jnp.exp(w["a_log"].astype(jnp.float32))
    conv_w, conv_b = (w[k].astype(jnp.float32) for k in ("conv_w", "conv_b"))

    state, history = start if start is not None else (
        None, jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32))
    rows = jnp.concatenate([history, xbc], 0)                    # (S+k-1, w)
    at = jnp.arange(s)[:, None] + jnp.arange(taps)[None, :]      # (S, k)
    if fault == "tail_at_bucket_end":
        # a served token's window reaches back into the prompt: those rows
        # come from the prompt's start, not from its end
        first = prompt_len - (taps - 1)         # the tail's first row
        reaches = ((jnp.arange(s)[:, None] >= prompt_len)
                   & (at - (taps - 1) < prompt_len))
        at = jnp.where(reaches, at - first, at)
    act = jax.nn.silu(jnp.einsum("skw,kw->sw", rows[at], conv_w) + conv_b)
    xs = act[:, :di].reshape(s, hn, p)
    b_, c_ = (act[:, lo:lo + g * n].reshape(s, g, n)
              for lo in (di, di + g * n))
    y, last = ssm_scan(xs, dt, a, b_, c_, state, swap)
    if fault != "no_d_skip":
        y = y + w["d"].astype(jnp.float32)[None, :, None] * xs
    y = y.reshape(s, di)
    gate, gain = jax.nn.silu(z), w["gate_norm"].astype(jnp.float32)
    group = lambda t: t.reshape(s, g, di // g)
    if fault == "gate_after_norm":
        y = _rms(group(y), gain.reshape(g, -1), dims["eps"]).reshape(s, di) \
            * gate
    else:
        y = _rms(group(y * gate), gain.reshape(g, -1),
                 dims["eps"]).reshape(s, di)
    return _mm(y, w["out_proj"], mode, "sk,kn->sn"), (last,
                                                      rows[-(taps - 1):])


# --------------------------------------------------------- *: attention

def attention(x, w, dims, mode="f32", fault=None):
    """Grouped-query causal attention over one sequence ``x`` (S, h)."""
    s = x.shape[0]
    hq, hk, d = dims["q_heads"], dims["kv_heads"], dims["head_dim"]
    q = _mm(x, w["q"], mode, "sk,kn->sn").reshape(s, hq, d)
    k = _mm(x, w["k"], mode, "sk,kn->sn").reshape(s, hk, d)
    v = _mm(x, w["v"], mode, "sk,kn->sn").reshape(s, hk, d)
    k, v = (jnp.repeat(t, hq // hk, axis=1) for t in (k, v))
    scores = _mm(q.transpose(1, 0, 2), k.transpose(1, 0, 2), mode,
                 "hqk,htk->hqt") * d ** -0.5
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    prob = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    o = _mm(prob, v.transpose(1, 2, 0), mode, "hqk,hdk->qhd")
    return _mm(o.reshape(s, hq * d), w["o"], mode, "sk,kn->sn")


# ------------------------------------------------------ E: latent experts

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
    _, chosen = lax.top_k(scores + w["choice_bias"].astype(jnp.float32),
                          dims["top_k"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    if dims["norm_topk"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    if fault != "no_routed_scale":
        picked = picked * dims["routed_scale"]
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(picked)


def experts(x, w, dims, mode="f32", fault=None, held=None):
    """The expert layer over ``x`` (S, h): every held expert in turn over
    all the tokens' latents, weighted by ``route`` (0 for a token that did
    not choose it), the sum through ``W_up``, plus the shared expert on
    ``x``.  ``held = (first, count)`` keeps the routed part to those
    experts' share (the router still scores all)."""
    weight = route(x, w, dims, mode, fault)
    first, count = held or dims.get("held") or (0, weight.shape[1])
    mine = slice(first, first + count)
    stored = slice(None) if w["w_up"].shape[0] == count else mine
    u = _mm(x, w["latent_down"], mode, "sk,kn->sn")

    def one(y, ew):
        up, down, col = ew
        return y + col[:, None] * _relu2_mlp(u, up, down, mode, fault), None

    y, _ = lax.scan(one, jnp.zeros_like(u),
                    (w["w_up"][stored], w["w_down"][stored], weight.T[mine]))
    y = _mm(y, w["latent_up"], mode, "sk,kn->sn")
    if fault != "no_shared":
        y = y + _relu2_mlp(x, w["shared_up"], w["shared_down"], mode, fault)
    return y


# ------------------------------------------------------------- the model

def hidden_fn(weights: dict, tokens, dims: dict, *, mode: str = "f32",
              fault: str | None = None, margins: bool = False,
              prompt_len=None, pads=0):
    """``(S,)`` token ids -> ``(S, h)`` float32 after the final norm; with
    ``margins`` also ``(S,)``, the least ``choice_margin`` of each position
    over the expert layers."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    if fault in ("pads_advance", "tail_at_bucket_end") and prompt_len is None:
        raise ValueError(f"{fault} is planted at a prompt_len")

    def forward(ids, mamba_kw):
        """The layers over ``ids``; ``mamba_kw(i)`` gives the i-th
        state-space layer's further arguments.  Returns the hidden rows,
        the least margins and where each state-space layer ended."""
        x = weights["embed"][ids].astype(jnp.float32)
        least = jnp.full(ids.shape, jnp.inf, jnp.float32)
        ends = []
        for w in weights["layers"]:
            y = _rms(x, w["norm"], dims["eps"])
            if "in_proj" in w:
                y, end = mamba(y, w, dims, mode, fault, prompt_len,
                               **mamba_kw(len(ends)))
                ends.append(end)
            elif "router" in w:
                if margins:
                    least = jnp.minimum(least, choice_margin(y, w, dims))
                y = experts(y, w, dims, mode, fault)
            else:
                y = attention(y, w, dims, mode, fault)
            x = x + y
        return _rms(x, weights["final_norm"], dims["eps"]), least, ends

    kw = lambda i: {}
    if fault == "state_kept":
        _, _, left = forward((tokens + 1) % weights["embed"].shape[0], kw)
        kw = lambda i: {"start": left[i]}
    if fault == "pads_advance":
        # where a prefill whose pads were ordinary tokens leaves each
        # layer: the prompt, then ``pads`` tokens of id 0, then nothing
        at = jnp.arange(tokens.shape[0])
        _, _, after_bucket = forward(
            jnp.where(at < prompt_len, tokens, 0),
            lambda i: {"still_from": prompt_len + pads})
        kw = lambda i: {"swap": (at == prompt_len - 1, after_bucket[i][0])}
    x, least, _ = forward(tokens, kw)
    return (x, least) if margins else x


def head_fn(weights: dict, hidden, *, mode: str = "f32"):
    """``(n, h)`` normed hidden rows -> ``(n, V)`` float32 logits."""
    return _mm(hidden, weights["head"], mode, "sk,kv->sv")


def logits_fn(weights: dict, tokens, dims: dict, *, mode: str = "f32",
              fault: str | None = None, **kw):
    """``(S,)`` token ids -> ``(S, V)`` float32 next-token logits."""
    return head_fn(weights, hidden_fn(weights, tokens, dims, mode=mode,
                                      fault=fault, **kw), mode=mode)
