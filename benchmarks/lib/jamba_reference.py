"""Plain selective-scan (Mamba-1) / multi-query-attention hybrid decoder
(the ``jamba`` block family with one expert): the full causal forward in
straightforward jnp.

float32 with ``highest`` matmul precision; the recurrence as a plain
``lax.scan`` of its one-token update over the sequence, attention over the
whole sequence under a mask a block of queries at a time (the score tile of
20 heads x 32,768 keys is what has to fit), the feed-forward a block of rows
at a time, no kernels, no cache, no batching; imports nothing of the
program.  Each weight is raised to float32 where it is used, so a sequence
of 32,768 tokens at published widths fits beside the bfloat16 weights on one
chip.  ``tests/jamba_reference.py`` is a copy of this file
(``tests/test_jamba.py`` holds the two equal).

    layer i:  x = x + Mixer_i(RMS(x));  x = x + W_down(silu(W_gate n) * (W_up n)),  n = RMS'(x)
    logits = RMS_final(x) E^T                  (E the embedding, tied)

``weights`` (``lib/jamba_weights.py`` makes them; any float dtype):

    embed (V, h), final_norm (h,), layers: a list of
      norm (h,), ffn_norm (h,), gate, up (h, f), down (f, h) and one of
      M  in_proj (h, 2 d_i)  columns [u | z], conv_w (k, d_i), conv_b (d_i,),
         x_proj (d_i, R + 2 N)  columns [r | B | C], dt_norm (R,),
         b_norm (N,), c_norm (N,), dt_proj (R, d_i), dt_bias (d_i,),
         a_log (d_i, N), d (d_i,), out_proj (d_i, h)
      A  q (h, H_q d), k, v (h, H_kv d), o (H_q d, h)

``dims`` (``dims_of`` reads them off a ``config.json`` of the family):
q_heads, kv_heads, head_dim, state N, dt_rank R, eps.

M, from ``S_0 = 0``: ``u = silu(conv(u) + b)`` (causal, depthwise, k taps),
``r, B, C = RMS(r), RMS(B), RMS(C)`` with a gain each, ``dt = softplus(r
W_dt + b_dt)``, ``S_t[d, n] = exp(dt_t[d] A[d, n]) S_{t-1}[d, n] + dt_t[d]
u_t[d] B_t[n]``, ``y_t[d] = sum_n S_t[d, n] C_t[n] + D[d] u_t[d]``, ``out =
(y silu(z)) W_out``.  A: multi-query causal softmax attention at
``1/sqrt(d)`` with no position term.

Departures from the published code (transformers' ``modeling_jamba``):
everything is float32 where it runs bfloat16 with a float32 recurrence;
``num_experts`` 1 makes every feed-forward the dense SwiGLU, which is what
is written (no router).

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
    "scalar_decay",      # A[d, 0] for every n: one decay a channel, Mamba-2's
                         # form under this model's name
    "no_inner_norms",    # r, B, C used as x_proj gives them
    "no_d_skip",         # D u left out
    "no_conv_bias",      # the convolution's bias left out
    "no_dt_bias",        # b_dt left out
    "gate_before_scan",  # scan(u silu(z)) in place of scan(u) silu(z)
    "attn_off_by_one",   # the attention mixers one layer early (6 and 20
                         # of 28): each trades places with the mixer before
    "no_ssm_ffn",        # the feed-forward of the state-space layers left out
)

ROW_BLOCK = 4096         # rows of the feed-forward at a time
QUERY_BLOCK = 512        # queries of the attention at a time


def dims_of(cfg: dict) -> dict:
    heads = int(cfg["num_attention_heads"])
    return dict(q_heads=heads, kv_heads=int(cfg["num_key_value_heads"]),
                head_dim=int(cfg.get("head_dim")
                             or int(cfg["hidden_size"]) // heads),
                state=int(cfg["mamba_d_state"]),
                dt_rank=int(cfg["mamba_dt_rank"]),
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


def _static(fn):
    """``fn(x, w, dims, mode, fault, ...)`` jitted with ``dims``, ``mode``
    and ``fault`` static: called layer by layer outside any jit (the
    benchmark's driver at 32,768 positions), every layer of a kind shares
    one compiled program; inside a jit it is inlined."""
    jitted = jax.jit(lambda x, w, dims, mode, fault, kw: fn(
        x, w, dict(dims), mode, fault, **kw), static_argnums=(2, 3, 4))

    def call(x, w, dims, mode="f32", fault=None, **kw):
        return jitted(x, w, tuple(sorted(dims.items())), mode, fault, kw)
    return call


# ------------------------------------------------------- M: state space

def ssm_scan(u, dt, a, b, c, state=None, swap=None):
    """The recurrence, one token at a time.  ``u``, ``dt`` (S, d_i) (``dt``
    after the softplus), ``a`` (d_i, N) negative, ``b``, ``c`` (S, N).
    Returns ``sum_n S_t C_t`` (S, d_i) and the last state (d_i, N).  ``swap
    = (at (S,) bool, value)``: after the position where ``at`` is set the
    state becomes ``value`` (a planted fault)."""
    if state is None:
        state = jnp.zeros(a.shape, jnp.float32)
    at, value = swap if swap is not None \
        else (jnp.zeros(u.shape[0], bool), state)

    def step(s, inp):
        u_t, dt_t, b_t, c_t, swap_t = inp
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        y = jnp.sum(s * c_t[None, :], -1)
        return jnp.where(swap_t, value, s), y

    state, y = lax.scan(step, state, (u, dt, b, c, at))
    return y, state


@_static
def mamba(x, w, dims, mode="f32", fault=None, prompt_len=None,
          still_from=None, swap=None, start=None):
    """The state-space mixer over one sequence ``x`` (S, h); also where it
    ends: ``(last state, last taps - 1 pre-activation rows)``.  From
    position ``still_from`` on ``dt`` is 0 (the state stands still);
    ``swap`` as in ``ssm_scan``; ``start`` is such an end to begin from in
    place of zeros."""
    s = x.shape[0]
    n, rank, eps = dims["state"], dims["dt_rank"], dims["eps"]
    taps, di = w["conv_w"].shape
    proj = _mm(x, w["in_proj"], mode, "sk,kn->sn")
    pre, z = proj[:, :di], proj[:, di:]
    conv_w = w["conv_w"].astype(jnp.float32)

    state, history = start if start is not None else (
        None, jnp.zeros((taps - 1, di), jnp.float32))
    rows = jnp.concatenate([history, pre], 0)                   # (S+k-1, d_i)
    at = jnp.arange(s)[:, None] + jnp.arange(taps)[None, :]     # (S, k)
    if fault == "tail_at_bucket_end":
        # a served token's window reaches back into the prompt: those rows
        # come from the prompt's start, not from its end
        first = prompt_len - (taps - 1)         # the tail's first row
        reaches = ((jnp.arange(s)[:, None] >= prompt_len)
                   & (at - (taps - 1) < prompt_len))
        at = jnp.where(reaches, at - first, at)
    conv = sum(rows[at[:, j]] * conv_w[j] for j in range(taps))
    if fault != "no_conv_bias":
        conv = conv + w["conv_b"].astype(jnp.float32)
    u = jax.nn.silu(conv)
    gate = jax.nn.silu(z)
    low = _mm(u, w["x_proj"], mode, "sk,kn->sn")
    r, b, c = low[:, :rank], low[:, rank:rank + n], low[:, rank + n:]
    if fault != "no_inner_norms":
        r, b, c = (_rms(t, w[g], eps) for t, g in (
            (r, "dt_norm"), (b, "b_norm"), (c, "c_norm")))
    dt = _mm(r, w["dt_proj"], mode, "sk,kn->sn")
    if fault != "no_dt_bias":
        dt = dt + w["dt_bias"].astype(jnp.float32)
    dt = jax.nn.softplus(dt)
    if still_from is not None:
        dt = jnp.where(jnp.arange(s)[:, None] < still_from, dt, 0.0)
    a = -jnp.exp(w["a_log"].astype(jnp.float32))
    if fault == "scalar_decay":
        a = jnp.broadcast_to(a[:, :1], a.shape)
    fed = u * gate if fault == "gate_before_scan" else u
    y, last = ssm_scan(fed, dt, a, b, c, state, swap)
    if fault != "no_d_skip":
        y = y + w["d"].astype(jnp.float32) * fed
    if fault != "gate_before_scan":
        y = y * gate
    return _mm(y, w["out_proj"], mode, "sk,kn->sn"), (last,
                                                      rows[-(taps - 1):])


# --------------------------------------------------------- A: attention

@_static
def attention(x, w, dims, mode="f32", fault=None):
    """Multi-query causal attention over one sequence ``x`` (S, h), a block
    of queries at a time against every key under the mask."""
    s = x.shape[0]
    hq, hk, d = dims["q_heads"], dims["kv_heads"], dims["head_dim"]
    q = _mm(x, w["q"], mode, "sk,kn->sn").reshape(s, hq, d)
    k = _mm(x, w["k"], mode, "sk,kn->sn").reshape(s, hk, d)
    v = _mm(x, w["v"], mode, "sk,kn->sn").reshape(s, hk, d)
    k, v = (jnp.repeat(t, hq // hk, axis=1).transpose(1, 0, 2)
            for t in (k, v))                                    # (H, S, d)
    keys = jnp.arange(s)

    def block(q_b, at):                                 # (Q, H, d), (Q,)
        scores = _mm(q_b.transpose(1, 0, 2), k, mode, "hqk,htk->hqt") \
            * d ** -0.5
        prob = jax.nn.softmax(
            jnp.where(keys[None, :] <= at[:, None], scores, -jnp.inf), -1)
        return _mm(prob, v.transpose(0, 2, 1), mode, "hqk,hdk->qhd")

    o = _blocked(block, (q, keys), QUERY_BLOCK)
    return _mm(o.reshape(s, hq * d), w["o"], mode, "sk,kn->sn")


@_static
def swiglu(x, w, dims, mode="f32", fault=None):
    def rows(t):
        h = jax.nn.silu(_mm(t, w["gate"], mode, "sk,kn->sn")) \
            * _mm(t, w["up"], mode, "sk,kn->sn")
        return _mm(h, w["down"], mode, "sk,kn->sn")
    return _blocked(rows, (x,), ROW_BLOCK)


# ------------------------------------------------------------- the model

_MIXER_KEYS = ("in_proj", "conv_w", "conv_b", "x_proj", "dt_norm", "b_norm",
               "c_norm", "dt_proj", "dt_bias", "a_log", "d", "out_proj",
               "q", "k", "v", "o")


def _layers(weights: dict, fault):
    """The layers as the forward walks them; under ``attn_off_by_one`` each
    attention mixer has traded places with the mixer of the layer before
    (norms and feed-forwards stay)."""
    layers = list(weights["layers"])
    if fault != "attn_off_by_one":
        return layers
    split = lambda w: ({k: v for k, v in w.items() if k in _MIXER_KEYS},
                       {k: v for k, v in w.items() if k not in _MIXER_KEYS})
    for i, w in enumerate(weights["layers"]):
        if "q" in w and i > 0:
            (mix_a, rest_a), (mix_b, rest_b) = split(w), split(layers[i - 1])
            layers[i - 1], layers[i] = {**rest_b, **mix_a}, {**rest_a, **mix_b}
    return layers


def hidden_fn(weights: dict, tokens, dims: dict, *, mode: str = "f32",
              fault: str | None = None, prompt_len=None, pads=0):
    """``(S,)`` token ids -> ``(S, h)`` float32 after the final norm."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    if fault in ("pads_advance", "tail_at_bucket_end") and prompt_len is None:
        raise ValueError(f"{fault} is planted at a prompt_len")
    layers = _layers(weights, fault)

    def forward(ids, mamba_kw):
        """The layers over ``ids``; ``mamba_kw(i)`` gives the i-th
        state-space layer's further arguments.  Returns the hidden rows and
        where each state-space layer ended."""
        x = weights["embed"][ids].astype(jnp.float32)
        ends = []
        for w in layers:
            y = _rms(x, w["norm"], dims["eps"])
            if "in_proj" in w:
                y, end = mamba(y, w, dims, mode, fault, prompt_len=prompt_len,
                               **mamba_kw(len(ends)))
                ends.append(end)
            else:
                y = attention(y, w, dims, mode)
            x = x + y
            if not (fault == "no_ssm_ffn" and "in_proj" in w):
                x = x + swiglu(_rms(x, w["ffn_norm"], dims["eps"]), w, dims,
                               mode)
        return _rms(x, weights["final_norm"], dims["eps"]), ends

    kw = lambda i: {}
    if fault == "state_kept":
        _, left = forward((tokens + 1) % weights["embed"].shape[0], kw)
        kw = lambda i: {"start": left[i]}
    if fault == "pads_advance":
        # where a prefill whose pads were ordinary tokens leaves each
        # layer: the prompt, then ``pads`` tokens of id 0, then nothing
        at = jnp.arange(tokens.shape[0])
        _, after_bucket = forward(
            jnp.where(at < prompt_len, tokens, 0),
            lambda i: {"still_from": prompt_len + pads})
        kw = lambda i: {"swap": (at == prompt_len - 1, after_bucket[i][0])}
    return forward(tokens, kw)[0]


def head_fn(weights: dict, hidden, *, mode: str = "f32"):
    """``(n, h)`` normed hidden rows -> ``(n, V)`` float32 logits (the
    embedding, tied)."""
    return _mm(hidden, weights["embed"], mode, "sk,vk->sv")


def logits_fn(weights: dict, tokens, dims: dict, *, mode: str = "f32",
              fault: str | None = None, **kw):
    """``(S,)`` token ids -> ``(S, V)`` float32 next-token logits."""
    return head_fn(weights, hidden_fn(weights, tokens, dims, mode=mode,
                                      fault=fault, **kw), mode=mode)
