"""Flash attention as Pallas TPU kernels (forward + backward).

The reference has no attention anywhere (SURVEY.md §2.2 — its only model is
an MLP on 28×28, reference initializer.py:14-19).  This kernel is pure
TPU-native capability: softmax(QKᵀ)V computed blockwise so the (L, L) score
matrix never exists in HBM — scores live tile-by-tile in VMEM, the running
(max, sum, acc) merge keeps the math exact, and the MXU sees only dense
(block_q × d) @ (d × block_k) matmuls.

Three kernels:

* ``_fwd_kernel``   — grid (B·H, Lq/bq, Lk/bk): online-softmax accumulation
  into VMEM scratch, output + logsumexp written on the last k-step.
* ``_dkv_kernel``   — grid (B·H, Lk/bk, Lq/bq): recomputes p from the saved
  logsumexp, accumulates dK/dV for one k-block across all q-blocks.
* ``_dq_kernel``    — grid (B·H, Lq/bq, Lk/bk): accumulates dQ.

The TPU grid iterates its last dimension innermost/sequentially, which is
what lets the scratch accumulators persist across that dimension (the
standard Pallas flash pattern).  Under causal masking, fully-masked blocks
are skipped with `pl.when` — ~2× fewer FLOPs at long L.

Public entry: :func:`flash_attention` on (B, L, H, D) model-layout tensors,
with optional key-validity mask and causal masking, differentiable via
`jax.custom_vjp`.  On non-TPU backends the kernels run in Pallas interpret
mode, so the same code path is unit-testable on the CPU fake mesh
(SURVEY.md §4's test-strategy requirement).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

NEG_INF = -1e30  # matches parallel.ring_attention.NEG_INF: keeps exp()
                 # NaN-free when an entire row is masked
_TINY = 1e-30
_VMEM_BYTES = 16 * 2**20  # the scoped VMEM limit Mosaic enforces per kernel
                          # on the v5e, of 128 MiB physical (chip run, PR 21:
                          # 2048×2048 tiles at head 64 were refused with
                          # "Scoped allocation with size 20.99M and limit
                          # 16.00M exceeded scoped vmem limit"; 1024×2048 and
                          # the 512×1024 defaults compile).  The budget below
                          # validates block sizes BEFORE launching Mosaic


def _check_vmem_budget(bq: int, bk: int, d: int) -> None:
    """Fail fast (and clearly) when the requested blocks cannot fit VMEM.

    Per grid step the fwd kernel holds the (bq, bk) f32 score/prob tile,
    q/k/v blocks (bq·d + 2·bk·d) plus the f32 accumulators (~bq·d), with
    Pallas double-buffering the HBM-windowed operands.  An oversized
    choice otherwise surfaces as an opaque Mosaic allocation error deep in
    compilation.  The estimate (×2 for double buffering, f32 everywhere)
    is held against the scoped limit the compiler reported on the chip; it
    came within 5% of Mosaic's own figure where that was refused (20.1 MiB
    estimated for 2048×2048 at head 64, 20.99M reported).  Kernels near the
    line may still fail in Mosaic, but the common mistake (block_q/block_k
    sized like sequence lengths) is caught here."""
    tile = bq * bk * 4                       # score/prob tile, f32
    operands = 2 * (bq * d + 2 * bk * d) * 4  # q + k/v, double-buffered
    acc = 2 * bq * d * 4 + 2 * bq * 4        # out accumulator + m/l rows
    need = tile + operands + acc
    if need > _VMEM_BYTES:
        raise ValueError(
            f"flash attention blocks block_q={bq}, block_k={bk} with "
            f"head_dim={d} need ≈{need / 2**20:.0f} MiB of VMEM "
            f"(> {_VMEM_BYTES / 2**20:.0f} MiB): the (block_q × block_k) "
            f"f32 score tile must fit alongside the q/k/v blocks — use "
            f"smaller blocks (defaults 512/1024)")


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret mode (and the jnp twins behind it) is the CPU test lane.
    On a TPU backend the kernels always go through Mosaic: asking for the
    interpreter there is refused rather than obeyed, so no caller can end
    up off the compiled kernel on the chip."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU backend: the Pallas kernels compile "
            "with Mosaic there; the interpreter and its jnp twins are the "
            "CPU test lane")
    return not on_tpu if interpret is None else interpret


def join_vma(*xs) -> frozenset:
    """Union of the operands' varying-axes sets — pallas_call outputs must
    declare their vma explicitly when running inside `jax.shard_map`
    (check_vma); outside shard_map this is the empty set."""
    vma = frozenset()
    for x in xs:
        if x is not None:
            vma |= jax.typeof(x).vma
    return vma


def _block_spec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=_VMEM)


def _causal_skip(i, j, bq, bk):
    """True when k-block j is entirely in the future of q-block i."""
    return j * bk > i * bq + bq - 1


def _unless_skipped(causal, i, j, bq, bk, body):
    """Run ``body`` now, or under `pl.when` if causal skipping applies."""
    if causal:
        pl.when(jnp.logical_not(_causal_skip(i, j, bq, bk)))(body)
    else:
        body()


def _tile_mask(s, i, j, bq, bk, causal, mask_blk):
    """Apply causal + key-validity masking to a (bq, bk) score tile."""
    if causal:
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    return jnp.where(mask_blk > 0.0, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, bq, bk, nk):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _tile_mask(s, i, j, bq, bk, causal, mask_ref[0])

        m_prev, l_prev = m_scr[:], l_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    _unless_skipped(causal, i, j, bq, bk, compute)

    @pl.when(j == nk - 1)
    def _():
        l_safe = jnp.maximum(l_scr[:], _TINY)
        out_ref[0] = (acc_scr[:] / l_safe).astype(out_ref.dtype)
        lse_ref[0, 0] = (m_scr[:] + jnp.log(l_safe))[:, 0]


def _fwd(q, k, v, mask, scale, causal, bq, bk, interpret):
    """q (BH, Lq, D); k/v (BH, Lk, D); mask (BH, 1, Lk) → out, lse (BH, 1, Lq)."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    nq, nk = lq // bq, lk // bk

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk)
    # row-vector operands (mask, lse) carry a middle singleton dim so their
    # blocks are (1, 1, bL) — last two dims then satisfy the TPU tiling rule
    # (second-to-last == full array dim 1, last divisible by 128)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            _block_spec((1, bq, d), lambda b, i, j: (b, i, 0)),
            _block_spec((1, bk, d), lambda b, i, j: (b, j, 0)),
            _block_spec((1, bk, d), lambda b, i, j: (b, j, 0)),
            _block_spec((1, 1, bk), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=[
            _block_spec((1, bq, d), lambda b, i, j: (b, i, 0)),
            _block_spec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, d), q.dtype,
                                 vma=join_vma(q, k, v, mask)),
            jax.ShapeDtypeStruct((bh, 1, lq), jnp.float32,
                                 vma=join_vma(q, k, v, mask)),
        ],
        scratch_shapes=[
            _VMEM((bq, 1), jnp.float32),
            _VMEM((bq, 1), jnp.float32),
            _VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, mask)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, bq, bk, nq):
    j, i = pl.program_id(1), pl.program_id(2)  # k-block outer, q-block inner

    @pl.when(i == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _tile_mask(s, i, j, bq, bk, causal, mask_ref[0])
        p = jnp.exp(s - lse_ref[0, 0][:, None])                   # (bq, bk)
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                    # pᵀ·dO
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                    # dsᵀ·q

    _unless_skipped(causal, i, j, bq, bk, compute)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *, scale, causal, bq, bk, nk):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _tile_mask(s, i, j, bq, bk, causal, mask_ref[0])
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dq_scr[:] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    _unless_skipped(causal, i, j, bq, bk, compute)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd(q, k, v, mask, lse, delta, do, scale, causal, bq, bk, interpret):
    """delta = Σ_d do·out over the FULL attention output — callers computing
    blockwise/ring gradients pass the global delta (the flash backward math
    needs global lse + delta even for one k-block's contribution)."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    nq, nk = lq // bq, lk // bk

    qspec = _block_spec((1, bq, d), lambda b, x, y: (b, x, 0))
    kspec_q_outer = _block_spec((1, bk, d), lambda b, i, j: (b, j, 0))
    rowspec = _block_spec((1, 1, bq), lambda b, x, y: (b, 0, x))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk),
        grid=(bh, nq, nk),
        in_specs=[qspec, kspec_q_outer, kspec_q_outer,
                  _block_spec((1, 1, bk), lambda b, i, j: (b, 0, j)),
                  qspec, rowspec, rowspec],
        out_specs=[qspec],
        out_shape=[jax.ShapeDtypeStruct(
            q.shape, q.dtype, vma=join_vma(q, k, v, mask, do, lse, delta))],
        scratch_shapes=[_VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, mask, do, lse, delta)[0]

    # k-block is the second grid dim here (accumulator persists over q-blocks)
    qspec_k_outer = _block_spec((1, bq, d), lambda b, j, i: (b, i, 0))
    kspec = _block_spec((1, bk, d), lambda b, j, i: (b, j, 0))
    rowspec_k_outer = _block_spec((1, 1, bq), lambda b, j, i: (b, 0, i))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq),
        grid=(bh, nk, nq),
        in_specs=[qspec_k_outer, kspec, kspec,
                  _block_spec((1, 1, bk), lambda b, j, i: (b, 0, j)),
                  qspec_k_outer, rowspec_k_outer, rowspec_k_outer],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct(
                       k.shape, k.dtype,
                       vma=join_vma(q, k, v, mask, do, lse, delta)),
                   jax.ShapeDtypeStruct(
                       v.shape, v.dtype,
                       vma=join_vma(q, k, v, mask, do, lse, delta))],
        scratch_shapes=[_VMEM((bk, d), jnp.float32),
                        _VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, mask, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# differentiable core on (BH, L, D) arrays
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_core(q, k, v, mask, scale, causal, bq, bk, interpret):
    out, _ = _fwd(q, k, v, mask, scale, causal, bq, bk, interpret)
    return out


def _flash_core_fwd(q, k, v, mask, scale, causal, bq, bk, interpret):
    out, lse = _fwd(q, k, v, mask, scale, causal, bq, bk, interpret)
    return out, (q, k, v, mask, out, lse)


def _flash_core_bwd(scale, causal, bq, bk, interpret, res, do):
    q, k, v, mask, out, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True).transpose(0, 2, 1)     # (BH, 1, Lq)
    dq, dk, dv = _bwd(q, k, v, mask, lse, delta, do,
                      scale, causal, bq, bk, interpret)
    return dq, dk, dv, jnp.zeros_like(mask)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = False,
                    scale: float | None = None, kv_mask=None,
                    block_q: int = 512, block_k: int = 1024,
                    interpret: bool | None = None):
    """Memory-efficient exact attention on model-layout tensors.

    Args:
      q: (B, Lq, H, D);  k, v: (B, Lk, H, D)  — same layout as
        `parallel.ring_attention.dense_attention` so the two are drop-in
        interchangeable inside models.
      causal: mask future positions (by absolute position, so Lq == Lk
        is expected when True).
      kv_mask: optional (B, Lk) key-validity mask (>0 == valid).
      block_q / block_k: VMEM tile sizes; clamped to the (padded) sequence
        lengths.  The (bq × bk) f32 score tile must fit VMEM alongside the
        q/k/v blocks (2 MB at the defaults).
      interpret: force Pallas interpret mode; default = auto (True off-TPU).

    Returns (B, Lq, H, D).  Rows with no valid key return 0 (same guard as
    ring_attention).
    """
    interpret = resolve_interpret(interpret)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5

    mask = kv_mask if kv_mask is not None else jnp.ones((b, lk), jnp.float32)
    mask = mask.astype(jnp.float32)

    if interpret and join_vma(q, k, v, mask):
        # inside shard_map on a non-TPU backend: Pallas's HLO interpreter
        # cannot currently lower under vma checking, so run the pure-jnp
        # kernel twin (identical math incl. NEG_INF/_TINY guards, and
        # differentiable by plain AD).  The real kernel covers TPU and
        # standalone-interpret tests; test_flash_block_primitives_match_
        # kernel ties the two together.
        out, _ = _fwd_block_ref(q, k, v, mask, scale, causal)
        return out

    bq = min(block_q, lq)
    bk = min(block_k, lk)
    if not interpret:  # the interpreter has no VMEM to budget
        _check_vmem_budget(bq, bk, d)
    pad_q = (-lq) % bq
    pad_k = (-lk) % bk
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, pad_k)))  # padded keys invalid (0)
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))

    # (B, L, H, D) → (B·H, L, D); mask broadcasts per head
    def to_bh(x):
        return jnp.moveaxis(x, 2, 1).reshape(b * h, x.shape[1], d)

    # (B·H, 1, Lk): row b·H+h ← batch b; middle singleton for TPU tiling
    mask_bh = jnp.repeat(mask, h, axis=0)[:, None, :]
    out = _flash_core(to_bh(q), to_bh(k), to_bh(v), mask_bh,
                      scale, causal, bq, bk, interpret)
    out = jnp.moveaxis(out.reshape(b, h, lq + pad_q, d), 1, 2)
    if pad_q:
        out = out[:, :lq]
    return out


# ---------------------------------------------------------------------------
# blockwise primitives for ring attention (parallel/ring_attention.py)
# ---------------------------------------------------------------------------
#
# The ring schedule needs the kernel's RAW outputs — per-block (out, lse) on
# the forward, per-block (dq, dk, dv) given the GLOBAL lse/delta on the
# backward — because the cross-block softmax merge and the cross-device
# gradient accumulation happen at the ring layer, under its own custom_vjp.
# These wrappers only adapt layouts ((B, L, H, D) model layout ↔ the
# kernels' (B·H, L, D)) and handle block padding; they are NOT
# differentiable entry points themselves.

def _pad_seq(x, multiple):
    pad = (-x.shape[1]) % multiple
    if pad:
        cfg = [(0, 0)] * x.ndim
        cfg[1] = (0, pad)
        x = jnp.pad(x, cfg)
    return x, pad


def _to_bh(x):
    b, l, h, d = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(b * h, l, d)


def _from_bh(x, b, h):
    bh, l, d = x.shape
    return jnp.moveaxis(x.reshape(b, h, l, d), 1, 2)


def _block_scores_masked(q, k, kv_mask, scale, causal):
    """f32 masked scores for one (q-block, k-block) pair, (B, H, Lq, Lk)."""
    s = jnp.einsum("blhd,bmhd->bhlm", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        qpos = jnp.arange(s.shape[-2])[:, None]
        kpos = jnp.arange(s.shape[-1])[None, :]
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    return jnp.where(kv_mask[:, None, None, :] > 0, s, NEG_INF)


def _fwd_block_ref(q, k, v, kv_mask, scale, causal):
    """Pure-jnp twin of the forward kernel for one block pair — the
    interpret-mode path: Pallas's HLO interpreter cannot currently lower
    inside `jax.shard_map`'s vma checking, so CPU-mesh tests of the ring
    schedule run this (bit-matching math incl. the NEG_INF/_TINY guards);
    the real kernels cover the same math on TPU and standalone-interpret
    tests (tests/test_flash_attention.py)."""
    s = _block_scores_masked(q, k, kv_mask, scale, causal)
    m = s.max(axis=-1)                                     # (B, H, Lq)
    p = jnp.exp(s - m[..., None])
    l = jnp.maximum(p.sum(axis=-1), _TINY)
    out = jnp.einsum("bhlm,bmhd->blhd", p, v.astype(jnp.float32))
    out = out / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype), m + jnp.log(l)


def _bwd_block_ref(q, k, v, kv_mask, do, lse, delta, scale, causal):
    """Pure-jnp twin of the backward kernels for one block pair (see
    _fwd_block_ref); p is recovered from the GLOBAL lse."""
    s = _block_scores_masked(q, k, kv_mask, scale, causal)
    p = jnp.exp(s - lse[..., None])                        # (B, H, Lq, Lk)
    do32 = do.astype(jnp.float32)
    dv = jnp.einsum("bhlm,blhd->bmhd", p, do32)
    dp = jnp.einsum("blhd,bmhd->bhlm", do32, v.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhlm,bmhd->blhd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhlm,blhd->bmhd", ds, q.astype(jnp.float32))
    return dq, dk, dv


def flash_fwd_block(q, k, v, kv_mask, *, scale, causal=False,
                    block_q: int = 512, block_k: int = 1024,
                    interpret: bool | None = None):
    """One flash forward over a (q-block, k-block) pair.

    q: (B, Lq, H, D); k/v: (B, Lk, H, D); kv_mask: (B, Lk) (>0 valid).
    Returns (out (B, Lq, H, D) in q.dtype, lse (B, H, Lq) f32).  ``causal``
    means the pair sits on the ring's diagonal (identical global offsets);
    off-diagonal causal blocks are entirely-past (causal=False) or
    entirely-future (skipped by the caller)."""
    interpret = resolve_interpret(interpret)
    if interpret:
        return _fwd_block_ref(q, k, v, kv_mask, scale, causal)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    bq, bk = min(block_q, lq), min(block_k, lk)
    _check_vmem_budget(bq, bk, d)
    q, pad_q = _pad_seq(q, bq)
    k, _ = _pad_seq(k, bk)
    v, pad_k = _pad_seq(v, bk)
    mask = kv_mask.astype(jnp.float32)
    if pad_k:
        mask = jnp.pad(mask, ((0, 0), (0, pad_k)))
    mask_bh = jnp.repeat(mask, h, axis=0)[:, None, :]
    out, lse = _fwd(_to_bh(q), _to_bh(k), _to_bh(v), mask_bh,
                    scale, causal, bq, bk, interpret)
    out = _from_bh(out, b, h)[:, :lq]
    lse = lse.reshape(b, h, lq + pad_q)[:, :, :lq]
    return out, lse


def flash_bwd_block(q, k, v, kv_mask, do, lse, delta, *, scale, causal=False,
                    block_q: int = 512, block_k: int = 1024,
                    interpret: bool | None = None):
    """Per-block gradients given the GLOBAL softmax statistics.

    lse/delta: (B, H, Lq) — log-sum-exp of the FULL row and Σ_d do·out of
    the FULL output (flash's backward recovers this block's probabilities
    as exp(s − lse)).  Returns (dq, dk, dv) in f32, each the contribution
    of this (q-block, k-block) pair alone."""
    interpret = resolve_interpret(interpret)
    if interpret:
        return _bwd_block_ref(q, k, v, kv_mask, do, lse, delta, scale,
                              causal)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    bq, bk = min(block_q, lq), min(block_k, lk)
    _check_vmem_budget(bq, bk, d)
    q, pad_q = _pad_seq(q, bq)
    do, _ = _pad_seq(do, bq)
    k, _ = _pad_seq(k, bk)
    v, pad_k = _pad_seq(v, bk)
    mask = kv_mask.astype(jnp.float32)
    if pad_k:
        mask = jnp.pad(mask, ((0, 0), (0, pad_k)))
    if pad_q:
        # padded q rows: lse NEG_INF ⇒ p = exp(s − (−∞)) would blow up;
        # use +large lse instead so p underflows to 0 and contributes nothing
        pad_rows = ((0, 0), (0, 0), (0, pad_q))
        lse = jnp.pad(lse, pad_rows, constant_values=-NEG_INF)
        delta = jnp.pad(delta, pad_rows)
    mask_bh = jnp.repeat(mask, h, axis=0)[:, None, :]
    lse_bh = lse.reshape(b * h, 1, lq + pad_q)
    delta_bh = delta.astype(jnp.float32).reshape(b * h, 1, lq + pad_q)
    dq, dk, dv = _bwd(
        _to_bh(q).astype(jnp.float32), _to_bh(k).astype(jnp.float32),
        _to_bh(v).astype(jnp.float32), mask_bh, lse_bh, delta_bh,
        _to_bh(do).astype(jnp.float32), scale, causal, bq, bk, interpret)
    dq = _from_bh(dq, b, h)[:, :lq]
    dk = _from_bh(dk, b, h)[:, :lk]
    dv = _from_bh(dv, b, h)[:, :lk]
    return dq, dk, dv
