"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no attention and no sequence axis anywhere (SURVEY.md §2.2:
its only model is an MLP on 28×28, reference initializer.py:14-19) — this
module is TPU-native *new* capability required for long-context training:
sequences longer than one device's memory are sharded over a ``seq`` mesh
axis and attention runs without ever materializing the full (L, L) score
matrix on one chip.

Two standard strategies, both built on the L1 collectives layer:

* **Ring attention** (`ring_attention`): K/V blocks rotate around the mesh
  ring via `ppermute` while each device's Q stays put; partial softmax
  results merge with the numerically-stable running log-sum-exp (the
  blockwise/flash accumulation).  Communication is nearest-neighbor only —
  the cheapest pattern on a TPU torus (ICI), overlapping compute with the
  next block's transfer.
* **Ulysses** (`ulysses_attention`): `all_to_all` reshards activations from
  sequence-sharded to head-sharded, runs ordinary dense attention on full
  sequences for a subset of heads, and reshards back.  Needs
  ``num_heads % axis_size == 0``.

All functions must be called inside `jax.shard_map` with the sequence dim
sharded over ``axis``.  Shapes: q/k/v are (batch, seq_local, heads, head_dim).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.parallel.collectives import ring_shift

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() NaN-free when
                 # an entire block is masked (first causal blocks)


def _block_scores(q, k, scale):
    # (B, Lq, H, D) x (B, Lk, H, D) -> (B, H, Lq, Lk)
    return jnp.einsum("blhd,bmhd->bhlm", q, k) * scale


def dense_attention(q, k, v, causal: bool = False, scale: float | None = None,
                    kv_mask=None, prob_fn=None):
    """Single-device reference attention (test oracle and small-seq path).

    ``kv_mask``: optional key-validity mask; masked keys get NEG_INF.
    (B, Lk) applies per batch row to every query; (B, Lq, Lk) applies per
    QUERY — the multi-position slot-decode verify step needs each query in
    a token block to see only cache positions at or before its own.
    ``prob_fn``: optional transform of the post-softmax probabilities —
    the hook for attention-probability dropout (blockwise ring attention
    cannot support it; flash-style implementations conventionally drop it).
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = _block_scores(q, k, scale)
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        qpos = jnp.arange(lq)[:, None]
        kpos = jnp.arange(lk)[None, :]
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    if kv_mask is not None:
        m = (kv_mask[:, None, :, :] if kv_mask.ndim == 3
             else kv_mask[:, None, None, :])
        s = jnp.where(m > 0, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if prob_fn is not None:
        p = prob_fn(p)
    return jnp.einsum("bhlm,bmhd->blhd", p, v)


def ring_attention(q, k, v, axis: str, causal: bool = False,
                   scale: float | None = None, kv_mask=None):
    """Blockwise ring attention over the ``axis`` mesh ring.

    Device i holds Q/K/V for sequence block i.  At ring step t it attends
    Q_i against the K/V block that originated at device (i - t) mod n, then
    passes its current K/V to device i+1.  After n steps every Q block has
    seen every K/V block; the running (max, sum, acc) merge makes the result
    exactly softmax(QKᵀ)V, independent of arrival order.
    """
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    b, lq, h, d = q.shape
    lk = k.shape[1]

    # derive from k so the mask inherits k's varying-axes type (the fori_loop
    # carry requires input/output types — incl. vma — to match exactly)
    mask0 = kv_mask if kv_mask is not None else jnp.ones_like(k[..., 0, 0])

    def process(t, m, l, acc, k_cur, v_cur, mk_cur):
        src = (idx - t) % n  # which global block k_cur/v_cur came from
        s = _block_scores(q, k_cur, scale)  # (B,H,Lq,Lk)
        if causal:
            qpos = idx * lq + jnp.arange(lq)
            kpos = src * lk + jnp.arange(lk)
            s = jnp.where(qpos[:, None] >= kpos[None, :], s, NEG_INF)
        s = jnp.where(mk_cur[:, None, None, :] > 0, s, NEG_INF)
        m_blk = s.max(axis=-1)                     # (B,H,Lq)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new[..., None])          # (B,H,Lq,Lk)
        corr = jnp.exp(m - m_new)                  # (B,H,Lq)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhlm,bmhd->blhd", p, v_cur)
        acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
        return m_new, l_new, acc_new

    def body(t, carry):
        m, l, acc, k_cur, v_cur, mk_cur = carry
        # rotate-then-process: n-1 rotations total (the naive
        # process-then-rotate shape wastes a final dead K/V/mask transfer)
        k_cur, v_cur, mk_cur = ring_shift((k_cur, v_cur, mk_cur), axis)
        m, l, acc = process(t, m, l, acc, k_cur, v_cur, mk_cur)
        return m, l, acc, k_cur, v_cur, mk_cur

    # accumulators derived from q so they inherit q's varying-axes type
    # (works whether the surrounding shard_map has one mesh axis or several)
    qt = jnp.moveaxis(q[..., 0], 1, 2)  # (B, H, Lq)
    m0 = jnp.full_like(qt, NEG_INF)
    l0 = jnp.zeros_like(qt)
    acc0 = jnp.zeros_like(q)
    # block 0 (own K/V) costs no communication; the loop does the other n-1
    m, l, acc = process(0, m0, l0, acc0, k, v, mask0)
    if n > 1:
        m, l, acc, _, _, _ = lax.fori_loop(1, n, body, (m, l, acc, k, v, mask0))
    # rows with no unmasked key (impossible under causal self-attn, but keep
    # the division safe) fall back to 0
    l = jnp.maximum(l, 1e-30)
    return acc / l.transpose(0, 2, 1)[..., None]


def _ulysses(q, k, v, axis: str, causal: bool, scale, kv_mask, attn_fn):
    """Shared Ulysses reshard: (B, L/n, H, D) → (B, L, H/n, D) with one
    `all_to_all`, run ``attn_fn`` on the full sequence for H/n heads,
    reshard back.  Two all-to-alls per tensor vs n ppermute hops for ring —
    better when H divides well and the local math handles the full
    sequence."""
    n = lax.axis_size(axis)
    if q.shape[2] % n != 0:
        raise ValueError(f"num_heads {q.shape[2]} not divisible by axis size {n}")

    def to_heads(x):  # (B, L/n, H, D) -> (B, L, H/n, D)
        return lax.all_to_all(x, axis_name=axis, split_axis=2, concat_axis=1,
                              tiled=True)

    def to_seq(x):    # (B, L, H/n, D) -> (B, L/n, H, D)
        return lax.all_to_all(x, axis_name=axis, split_axis=1, concat_axis=2,
                              tiled=True)

    full_mask = None
    if kv_mask is not None:  # (B, L/n) → (B, L): every device needs all keys
        full_mask = lax.all_gather(kv_mask, axis_name=axis, axis=1, tiled=True)
    out = attn_fn(to_heads(q), to_heads(k), to_heads(v),
                  causal=causal, scale=scale, kv_mask=full_mask)
    return to_seq(out)


def ulysses_attention(q, k, v, axis: str, causal: bool = False,
                      scale: float | None = None, kv_mask=None):
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism with XLA
    dense local attention — see ``_ulysses``."""
    return _ulysses(q, k, v, axis, causal, scale, kv_mask, dense_attention)


def ulysses_flash_attention(q, k, v, axis: str, causal: bool = False,
                            scale: float | None = None, kv_mask=None):
    """Ulysses reshard with the Pallas flash kernel as the local math.

    After the all-to-all each device holds the FULL sequence for H/n
    heads — exactly the single-device flash case, so the fused kernel
    (ops/flash_attention.py: on-chip tiles, never materializes the (L, L)
    scores, causal block skipping, custom-vjp backward) applies verbatim.
    The communication pattern is identical to ``ulysses_attention``; only
    the O(L²) local compute changes — the same relationship ring_flash
    has to ring."""
    from distributed_tensorflow_tpu.ops.flash_attention import flash_attention

    return _ulysses(q, k, v, axis, causal, scale, kv_mask, flash_attention)


# ---------------------------------------------------------------------------
# ring attention with Pallas flash local math
# ---------------------------------------------------------------------------
#
# Same schedule as `ring_attention`, but each (Q_i, K_src) pairing runs the
# flash kernel (ops/flash_attention.py) instead of XLA blockwise math: the
# local (Lq, Lk) score tile lives in VMEM, never HBM.  The cross-block
# softmax merge happens here on the kernels' (out, lse) pairs, and — because
# the kernel wrappers are raw primitives, not differentiable — the whole
# ring carries its own `jax.custom_vjp`: the backward runs a second ring
# pass in which (k, v, dk, dv) rotate together and every device adds its
# block's contribution from the flash backward kernels, using the GLOBAL
# lse/delta saved from the forward (the standard ring-flash-attention
# decomposition).
#
# Causal masking never needs in-kernel positional offsets: a block pairing
# is entirely past (src < idx → plain full attention), diagonal (src == idx
# → the kernel's own causal mask), or entirely future (skipped — the ring
# analogue of the kernel's `pl.when` block skipping, ~2× fewer FLOPs).
# The branches run under `lax.switch` on a device-varying index; they are
# collective-free (a pallas_call is not a collective), which is what makes
# per-device branching legal inside shard_map.


def _merge_blocks(acc, lse, out_b, lse_b):
    """Numerically-stable merge of (acc, lse) with a new block's (out, lse):
    softmax-weighted combination in f32."""
    lse_new = jnp.logaddexp(lse, lse_b)
    alpha = jnp.exp(lse - lse_new)       # (B, H, Lq)
    beta = jnp.exp(lse_b - lse_new)
    acc = (acc * alpha.transpose(0, 2, 1)[..., None]
           + out_b.astype(jnp.float32) * beta.transpose(0, 2, 1)[..., None])
    return acc, lse_new


def _ring_flash_fwd_pass(q, k, v, mask, axis, causal, scale, bq, bk,
                         interpret):
    from distributed_tensorflow_tpu.ops.flash_attention import flash_fwd_block

    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)

    def block(src, k_cur, v_cur, mk_cur):
        def full(_):
            return flash_fwd_block(q, k_cur, v_cur, mk_cur, scale=scale,
                                   causal=False, block_q=bq, block_k=bk,
                                   interpret=interpret)

        def diag(_):
            return flash_fwd_block(q, k_cur, v_cur, mk_cur, scale=scale,
                                   causal=True, block_q=bq, block_k=bk,
                                   interpret=interpret)

        def skip(_):
            qt = jnp.moveaxis(q[..., 0], 1, 2).astype(jnp.float32)
            return jnp.zeros_like(q), jnp.full_like(qt, NEG_INF)

        if not causal:
            return full(None)
        # 0: future (skip), 1: diagonal (causal), 2: past (full)
        branch = jnp.int32(0) + (src <= idx) + (src < idx)
        return lax.switch(branch, [skip, diag, full], None)

    qt = jnp.moveaxis(q[..., 0], 1, 2).astype(jnp.float32)  # (B, H, Lq)
    acc0 = jnp.zeros_like(q, dtype=jnp.float32)
    lse0 = jnp.full_like(qt, NEG_INF)
    out_b, lse_b = block(idx, k, v, mask)
    acc, lse = _merge_blocks(acc0, lse0, out_b, lse_b)

    def body(t, carry):
        acc, lse, k_cur, v_cur, mk_cur = carry
        k_cur, v_cur, mk_cur = ring_shift((k_cur, v_cur, mk_cur), axis)
        src = (idx - t) % n
        out_b, lse_b = block(src, k_cur, v_cur, mk_cur)
        acc, lse = _merge_blocks(acc, lse, out_b, lse_b)
        return acc, lse, k_cur, v_cur, mk_cur

    if n > 1:  # block 0 (own K/V) above costs no communication
        acc, lse, _, _, _ = lax.fori_loop(
            1, n, body, (acc, lse, k, v, mask))
    return acc.astype(q.dtype), lse


def _ring_flash_bwd_pass(q, k, v, mask, lse, delta, do, axis, causal, scale,
                         bq, bk, interpret):
    from distributed_tensorflow_tpu.ops.flash_attention import flash_bwd_block

    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)

    def block_grads(src, k_cur, v_cur, mk_cur):
        def full(_):
            return flash_bwd_block(q, k_cur, v_cur, mk_cur, do, lse, delta,
                                   scale=scale, causal=False, block_q=bq,
                                   block_k=bk, interpret=interpret)

        def diag(_):
            return flash_bwd_block(q, k_cur, v_cur, mk_cur, do, lse, delta,
                                   scale=scale, causal=True, block_q=bq,
                                   block_k=bk, interpret=interpret)

        def skip(_):
            return (jnp.zeros_like(q, dtype=jnp.float32),
                    jnp.zeros_like(k_cur, dtype=jnp.float32),
                    jnp.zeros_like(v_cur, dtype=jnp.float32))

        if not causal:
            return full(None)
        branch = jnp.int32(0) + (src <= idx) + (src < idx)
        return lax.switch(branch, [skip, diag, full], None)

    def accumulate(t, dq, k_cur, v_cur, mk_cur, dk_cur, dv_cur):
        src = (idx - t) % n
        dq_c, dk_c, dv_c = block_grads(src, k_cur, v_cur, mk_cur)
        return dq + dq_c, dk_cur + dk_c, dv_cur + dv_c

    def body(t, carry):
        dq, k_cur, v_cur, mk_cur, dk_cur, dv_cur = carry
        dq, dk_cur, dv_cur = accumulate(t, dq, k_cur, v_cur, mk_cur,
                                        dk_cur, dv_cur)
        # dk/dv ride WITH their k/v block so every device adds its
        # contribution to the right accumulator
        k_cur, v_cur, mk_cur, dk_cur, dv_cur = ring_shift(
            (k_cur, v_cur, mk_cur, dk_cur, dv_cur), axis)
        return dq, k_cur, v_cur, mk_cur, dk_cur, dv_cur

    dq0 = jnp.zeros_like(q, dtype=jnp.float32)
    dk0 = jnp.zeros_like(k, dtype=jnp.float32)
    dv0 = jnp.zeros_like(v, dtype=jnp.float32)
    # n-1 full process+rotate rounds, then the last block's accumulation
    # with a final hop of ONLY (dk, dv) — k/v/mask values would be
    # discarded after it (the same dead-transfer avoidance the forward
    # ring documents)
    dq, k_l, v_l, mk_l, dk_l, dv_l = lax.fori_loop(
        0, n - 1, body, (dq0, k, v, mask, dk0, dv0))
    dq, dk_l, dv_l = accumulate(n - 1, dq, k_l, v_l, mk_l, dk_l, dv_l)
    if n > 1:
        dk_l, dv_l = ring_shift((dk_l, dv_l), axis)
    return dq.astype(q.dtype), dk_l.astype(k.dtype), dv_l.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ring_flash(q, k, v, mask, axis, causal, scale, bq, bk, interpret):
    out, _ = _ring_flash_fwd_pass(q, k, v, mask, axis, causal, scale,
                                  bq, bk, interpret)
    return out


def _ring_flash_fwd(q, k, v, mask, axis, causal, scale, bq, bk, interpret):
    out, lse = _ring_flash_fwd_pass(q, k, v, mask, axis, causal, scale,
                                    bq, bk, interpret)
    return out, (q, k, v, mask, out, lse)


def _ring_flash_bwd(axis, causal, scale, bq, bk, interpret, res, do):
    q, k, v, mask, out, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)               # (B, H, Lq)
    dq, dk, dv = _ring_flash_bwd_pass(q, k, v, mask, lse, delta, do,
                                      axis, causal, scale, bq, bk, interpret)
    return dq, dk, dv, jnp.zeros_like(mask)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(q, k, v, axis: str, causal: bool = False,
                         scale: float | None = None, kv_mask=None,
                         block_q: int = 512, block_k: int = 1024,
                         interpret: bool | None = None):
    """Ring attention whose local block math is the Pallas flash kernel.

    Drop-in for :func:`ring_attention` (same contract: call inside
    `jax.shard_map` with the sequence dim sharded over ``axis``); the
    difference is WHERE the block scores live — flash keeps each
    (Lq, Lk_block) tile in VMEM instead of materializing it in HBM, and
    entirely-future causal blocks are skipped without launching a kernel."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    mask = (kv_mask if kv_mask is not None
            else jnp.ones_like(k[..., 0, 0]))
    mask = mask.astype(jnp.float32)
    return _ring_flash(q, k, v, mask, axis, causal, scale,
                       block_q, block_k, interpret)
