"""Flash attention as Pallas TPU kernels (forward + backward).

The reference has no attention anywhere (SURVEY.md §2.2 — its only model is
an MLP on 28×28, reference initializer.py:14-19).  This kernel is pure
TPU-native capability: softmax(QKᵀ)V computed blockwise so the (L, L) score
matrix never exists in HBM — scores live piece by piece in VMEM, the running
(max, sum, acc) merge keeps the math exact, and the MXU sees only dense
products.

Three kernels, one `pallas_call` each:

* ``_fwd_kernel``   — grid (B·H, Lq/bq, Lk/bk): online-softmax accumulation
  into VMEM scratch, output + logsumexp written on the last k-step.
* ``_dq_kernel``    — grid (B·H, Lq/bq, Lk/bk): recomputes p from the saved
  logsumexp and accumulates dQ.
* ``_dkv_kernel``   — grid (B·H, Lk/bk, Lq/bq): the same recomputation,
  accumulating dK/dV for one k-block across all q-blocks.

The TPU grid iterates its last dimension innermost/sequentially, which is
what lets the scratch accumulators persist across that dimension (the
standard Pallas flash pattern).

What one grid step does (PR 34).  All three kernels hold the tile
TRANSPOSED, keys down and queries across, and walk it a strip of queries
at a time (`_pieces`): a piece is (keys × strip).  That orientation is what
the v5e wants: a query's statistics (running max and sum, lse, delta) are
row vectors that lie along the lanes, so they reduce across sublanes,
broadcast as they lie and are stored as they lie; the long side of every
product is the key side.  The outputs that belong to queries (the
attention output, dQ) therefore leave the kernel as (D, Lq) and the
wrapper turns them.  The MXU gets its operands in the dtype they arrive in
(bfloat16 from a bf16 model, float32 from a float32 caller; ``p`` and
``ds`` are cast to it just before their product) and accumulates in
float32; scores, statistics and accumulators stay float32.  ``scale`` is
folded into the (bq, d) query tile.  Under causal masking a tile is one of
three kinds, decided from its position: wholly in the future (skipped with
`pl.when`, and its fetch saved by clamping the index map to the last needed
block), wholly in the past (no iota, compare or select) or on the
diagonal.  A square diagonal tile is a staircase: strip c of the queries
meets only the keys up to its own end, and only the last strip-width of
those keys is masked.  So what runs depends on the strip, not on the
block, and a block as long as the sequence (one grid step a head at
L 1,024) still skips the future: `causal_tiles` counts it.  A key-validity
mask is an operand only when the caller passed one.

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
                          # "Scoped allocation with size 20.99M and limit
                          # 16.00M exceeded scoped vmem limit").  The budget
                          # below validates block sizes BEFORE launching
                          # Mosaic
_NT = (((1,), (1,)), ((), ()))  # a·bᵀ: contract the minor dim of both
_TN = (((0,), (0,)), ((), ()))  # aᵀ·b: contract the major dim of both

# Queries a piece takes, per kernel (my chip runs, PR 34, at (128, 1024, 64)
# bfloat16: PERF.md section 6 has the sizes tried).  Multiples of 128: a
# strip is a slice along the lanes.
_STRIP = {"fwd": 512, "dq": 256, "dkv": 128}
_BLOCKS = (1024, 512, 256, 128)     # block sizes `_choose_blocks` tries


def _strip_width(kernel: str, bq: int) -> int:
    """The kernel's strip where it cuts the q-block into whole strips,
    else the q-block itself (one piece a tile)."""
    want = _STRIP[kernel]
    return want if bq % want == 0 else bq


def _vmem_need(bq: int, bk: int, d: int, itemsize: int) -> int:
    """Bytes one grid step holds: the float32 arrays of one piece (scores,
    probabilities and, in the backward, dP and dS: (bk × strip) each), the
    q/k/v/dO blocks in the operands' own width (Pallas double-buffers what
    it windows out of HBM) and the float32 accumulators."""
    piece = 4 * bk * max(_strip_width(k, bq) for k in _STRIP) * 4
    operands = 2 * (2 * bq * d + 2 * bk * d) * itemsize
    acc = 2 * max(bq, bk) * d * 4 + 2 * bq * 4
    return piece + operands + acc


def _check_vmem_budget(bq: int, bk: int, d: int, itemsize: int) -> None:
    """Fail fast (and clearly) when the requested blocks cannot fit VMEM.

    An oversized choice otherwise surfaces as an opaque Mosaic allocation
    error deep in compilation.  `_vmem_need` is an estimate held against
    the scoped limit the compiler reported on the chip; kernels near the
    line may still fail in Mosaic, but the common mistake (block_q/block_k
    sized like sequence lengths) is caught here."""
    need = _vmem_need(bq, bk, d, itemsize)
    if need > _VMEM_BYTES:
        raise ValueError(
            f"flash attention blocks block_q={bq}, block_k={bk} with "
            f"head_dim={d} need ≈{need / 2**20:.0f} MiB of VMEM "
            f"(> {_VMEM_BYTES / 2**20:.0f} MiB): a (block_k × strip) f32 "
            f"piece must fit alongside the q/k/v blocks — use smaller "
            f"blocks (leave them unset for a choice that fits)")


def _choose_blocks(lq: int, lk: int, d: int, itemsize: int,
                   causal: bool) -> tuple[int, int]:
    """(block_q, block_k) from the shapes alone.

    Under ``causal`` the blocks are square, so that the diagonal tiles are
    staircases (`_pieces`), and as large as divides the lengths: the fewer
    grid steps, the less each costs (0.35 us a step on the v5e), and what
    is skipped is decided by the strip, not by the block.  Never a key
    block wider than the query block.  Without ``causal`` 512 × 1024.  All
    clamped to the lengths and halved, the larger first,
    until `_vmem_need` fits the scoped limit."""
    if causal:
        fit = [b for b in _BLOCKS if lq % b == 0 and lk % b == 0]
        bq = bk = min(fit[0] if fit else 512, lq, lk)
    else:
        bq, bk = min(512, lq), min(1024, lk)
    while _vmem_need(bq, bk, d, itemsize) > _VMEM_BYTES and max(bq, bk) > 128:
        if causal:
            bq = bk = bq // 2
        elif bk >= bq:
            bk //= 2
        else:
            bq //= 2
    return bq, bk


def _resolve_blocks(block_q, block_k, lq, lk, d, itemsize, causal):
    """The caller's blocks where given (clamped to the lengths), else
    `_choose_blocks`' — each on its own."""
    bq, bk = _choose_blocks(lq, lk, d, itemsize, causal)
    if block_q is not None:
        bq = min(block_q, lq)
    if block_k is not None:
        bk = min(block_k, lk)
    return bq, bk


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


def _causal_past(i, j, bq, bk):
    """True when k-block j is entirely in the past of q-block i: its last
    key is no later than the block's first query."""
    return j * bk + bk - 1 <= i * bq


def _needed_k(causal, bq, bk):
    """``(i, j) -> k-block to hold`` at a grid step of q-block i: j, or
    under ``causal`` the last one q-block i needs when j lies past it.  A
    skipped tile then asks for the block Pallas already holds, and nothing
    is fetched for a tile that computes nothing."""
    if not causal:
        return lambda i, j: j
    return lambda i, j: jnp.minimum(j, (i * bq + bq - 1) // bk)


def _needed_q(causal, bq, bk):
    """``(j, i) -> q-block to hold`` at a grid step of k-block j: the
    q-blocks before the first one k-block j needs are the skipped ones,
    clamped up to it."""
    if not causal:
        return lambda j, i: i
    return lambda j, i: jnp.maximum(i, (j * bk) // bq)


def _causal_keep(shape, q0, k0):
    """(keys, queries) bool: query position >= key position, for a piece
    whose first key is k0 and first query q0."""
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return qpos >= kpos


def _pieces(causal, i, j, bq, bk, sub, mask_ref, piece):
    """Walk tile (q-block i, k-block j) a strip of ``sub`` queries at a
    time: ``piece(keys, qs, fix)`` gets static slices into the k-block and
    the q-block and the function that masks its (keys × strip) scores
    (causal positions, and the k-block's validity where ``mask_ref`` is
    one).

    Without ``causal`` every tile is plain.  Under it a tile wholly in the
    future runs nothing, one wholly in the past is plain, and one the
    diagonal crosses pays for the mask: a square one (which lies ON the
    diagonal, i == j) as a staircase, each strip against the keys up to
    its own end with a select over the last ``sub`` of them; any other
    shape in full under a position mask."""
    strips = [slice(c, c + sub) for c in range(0, bq, sub)]
    # the keys lie down: their validity is a (bk, 1) column
    valid = None if mask_ref is None else mask_ref[0, 0][:, None] > 0.0

    def run(keys, qs, fix):
        if valid is None:
            piece(keys, qs, fix)
        else:
            piece(keys, qs, lambda s: jnp.where(valid[keys], fix(s), NEG_INF))

    def plain():
        for qs in strips:
            run(slice(0, bk), qs, lambda s: s)

    def staircase():
        keep = _causal_keep((sub, sub), 0, 0)
        for qs in strips:
            def fix(s, head=qs.start):
                tail = jnp.where(keep, s[head:], NEG_INF)
                return tail if not head else jnp.concatenate(
                    [s[:head], tail], axis=0)
            run(slice(0, qs.stop), qs, fix)

    def masked():
        for qs in strips:
            run(slice(0, bk), qs, lambda s, q0=qs.start: jnp.where(
                _causal_keep((bk, sub), i * bq + q0, j * bk), s, NEG_INF))

    if not causal:
        plain()
        return
    past, future = _causal_past(i, j, bq, bk), _causal_skip(i, j, bq, bk)
    pl.when(past)(plain)
    pl.when(jnp.logical_not(jnp.logical_or(past, future)))(
        staircase if bq == bk else masked)


def causal_tiles(lq: int, lk: int, bq: int, bk: int, causal: bool,
                 kernel: str = "dkv") -> tuple[int, int]:
    """(computed, all) of one head's score matrix, in strip × strip
    squares — what `_pieces` walks, counted the way it walks it.  A static
    count, the mechanism's own: 10 of 16 at L 1,024 for a strip of 256."""
    sub = _strip_width(kernel, bq)
    nq, nk = -(-lq // bq), -(-lk // bk)
    run = 0
    for i in range(nq):
        for j in range(nk):
            if causal and _causal_skip(i, j, bq, bk):
                continue
            stairs = causal and bq == bk and not _causal_past(i, j, bq, bk)
            run += sum(q0 + sub if stairs else bk
                       for q0 in range(0, bq, sub)) // sub
    return run, nq * nk * (bq // sub) * (bk // sub)


def _scaled(q_ref, scale):
    """The query tile times ``scale``, in the dtype it arrived in: one
    (bq, d) multiply where the scores would take a (bk, bq) one."""
    return (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, has_mask, bq, bk, nk, sub):
    q_ref, k_ref, v_ref = refs[:3]
    mask_ref = refs[3] if has_mask else None
    out_ref, lse_ref, m_scr, l_scr, acc_scr = refs[3 + has_mask:]
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = _scaled(q_ref, scale)

    def piece(keys, qs, fix):
        v = v_ref[0, keys, :]
        s = fix(jax.lax.dot_general(k_ref[0, keys, :], q[qs], _NT,
                                    preferred_element_type=jnp.float32))
        m_prev = m_scr[:, qs]
        m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:, qs] = m_new
        l_scr[:, qs] = l_scr[:, qs] * corr + p.sum(axis=0, keepdims=True)
        acc_scr[:, qs] = acc_scr[:, qs] * corr + jax.lax.dot_general(
            v, p.astype(v.dtype), _TN,
            preferred_element_type=jnp.float32)             # (p·v)ᵀ

    _pieces(causal, i, j, bq, bk, sub, mask_ref, piece)

    @pl.when(j == nk - 1)
    def _():
        l_safe = jnp.maximum(l_scr[:], _TINY)
        out_ref[0] = (acc_scr[:] / l_safe).astype(out_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l_safe)


# `_fwd` and `_bwd` are jitted so that a model's 24 layers trace and lower
# each kernel once, not 24 times (set-up: 3.3 s of tracing and lowering for
# a 24-layer backward pass where the unjitted calls took 10), and INLINE so
# that each call's operations keep the enclosing module's name in the
# compiled program: `benchmarks/metrics/kernel.flash_*_roofline.json` find
# the kernels as `%CausalSelfAttention_N`.
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8), inline=True)
def _fwd(q, k, v, mask, scale, causal, bq, bk, interpret):
    """q (BH, Lq, D); k/v (BH, Lk, D); mask (BH, 1, Lk) or None → out,
    lse (BH, 1, Lq)."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    nq, nk = lq // bq, lk // bk
    has_mask = mask is not None

    kj = _needed_k(causal, bq, bk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               has_mask=has_mask, bq=bq, bk=bk, nk=nk,
                               sub=_strip_width("fwd", bq))
    kspec = _block_spec((1, bk, d), lambda b, i, j: (b, kj(i, j), 0))
    # row-vector operands (mask, lse) carry a middle singleton dim so their
    # blocks are (1, 1, bL) — last two dims then satisfy the TPU tiling rule
    # (second-to-last == full array dim 1, last divisible by 128)
    mask_spec = [_block_spec((1, 1, bk), lambda b, i, j: (b, 0, kj(i, j)))]
    vma = join_vma(q, k, v, mask)
    out_t, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[_block_spec((1, bq, d), lambda b, i, j: (b, i, 0)),
                  kspec, kspec] + mask_spec * has_mask,
        out_specs=[
            _block_spec((1, d, bq), lambda b, i, j: (b, 0, i)),
            _block_spec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, d, lq), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, 1, lq), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            _VMEM((1, bq), jnp.float32),
            _VMEM((1, bq), jnp.float32),
            _VMEM((d, bq), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, *[mask] * has_mask)
    # the callers' own relayout to (B, L, H, D) follows: XLA makes one
    # transpose of the two
    return out_t.transpose(0, 2, 1), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _recomputed(k, q, v, do, lse, delta, fix):
    """(pᵀ, dsᵀ) of one piece from the saved statistics: k, v (keys, d);
    q (scaled), do (strip, d); lse, delta (1, strip)."""
    s = fix(jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32))
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    return p, p * (dp - delta)


def _dq_kernel(*refs, scale, causal, has_mask, bq, bk, nk, sub):
    q_ref, k_ref, v_ref = refs[:3]
    mask_ref = refs[3] if has_mask else None
    do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs[3 + has_mask:]
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q = _scaled(q_ref, scale)

    def piece(keys, qs, fix):
        k = k_ref[0, keys, :]
        _, ds = _recomputed(k, q[qs], v_ref[0, keys, :], do_ref[0, qs, :],
                            lse_ref[0, :, qs], delta_ref[0, :, qs], fix)
        dq_scr[:, qs] += jax.lax.dot_general(
            k, ds.astype(k.dtype), _TN,
            preferred_element_type=jnp.float32)             # (ds·k)ᵀ

    _pieces(causal, i, j, bq, bk, sub, mask_ref, piece)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, has_mask, bq, bk, nq, sub):
    q_ref, k_ref, v_ref = refs[:3]
    mask_ref = refs[3] if has_mask else None
    (do_ref, lse_ref, delta_ref,
     dk_ref, dv_ref, dk_scr, dv_scr) = refs[3 + has_mask:]
    j, i = pl.program_id(1), pl.program_id(2)  # k-block outer, q-block inner

    @pl.when(i == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q = _scaled(q_ref, scale)

    def piece(keys, qs, fix):
        qp, do = q[qs], do_ref[0, qs, :]
        p, ds = _recomputed(k_ref[0, keys, :], qp, v_ref[0, keys, :], do,
                            lse_ref[0, :, qs], delta_ref[0, :, qs], fix)
        dv_scr[keys] += jnp.dot(p.astype(do.dtype), do,
                                preferred_element_type=jnp.float32)  # pᵀ·dO
        # q carries the scale, so this is scale · dsᵀ·q
        dk_scr[keys] += jnp.dot(ds.astype(qp.dtype), qp,
                                preferred_element_type=jnp.float32)

    _pieces(causal, i, j, bq, bk, sub, mask_ref, piece)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10, 11), inline=True)
def _bwd(q, k, v, mask, lse, delta, do, scale, causal, bq, bk, interpret):
    """delta = Σ_d do·out over the FULL attention output — callers computing
    blockwise/ring gradients pass the global delta (the flash backward math
    needs global lse + delta even for one k-block's contribution)."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    nq, nk = lq // bq, lk // bk
    has_mask = mask is not None
    masks = [mask] * has_mask
    vma = join_vma(q, k, v, mask, do, lse, delta)

    kj = _needed_k(causal, bq, bk)
    qspec = _block_spec((1, bq, d), lambda b, i, j: (b, i, 0))
    kspec_q_outer = _block_spec((1, bk, d), lambda b, i, j: (b, kj(i, j), 0))
    rowspec = _block_spec((1, 1, bq), lambda b, i, j: (b, 0, i))
    mask_spec = [_block_spec((1, 1, bk), lambda b, i, j: (b, 0, kj(i, j)))]

    dq_t = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          has_mask=has_mask, bq=bq, bk=bk, nk=nk,
                          sub=_strip_width("dq", bq)),
        grid=(bh, nq, nk),
        in_specs=[qspec, kspec_q_outer, kspec_q_outer]
                 + mask_spec * has_mask + [qspec, rowspec, rowspec],
        out_specs=[_block_spec((1, d, bq), lambda b, i, j: (b, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((bh, d, lq), q.dtype, vma=vma)],
        scratch_shapes=[_VMEM((d, bq), jnp.float32)],
        interpret=interpret,
    )(q, k, v, *masks, do, lse, delta)[0]

    # k-block is the second grid dim here (accumulator persists over
    # q-blocks)
    qi = _needed_q(causal, bq, bk)
    qspec_k_outer = _block_spec((1, bq, d), lambda b, j, i: (b, qi(j, i), 0))
    kspec = _block_spec((1, bk, d), lambda b, j, i: (b, j, 0))
    rowspec_k_outer = _block_spec((1, 1, bq),
                                  lambda b, j, i: (b, 0, qi(j, i)))
    mask_spec = [_block_spec((1, 1, bk), lambda b, j, i: (b, 0, j))]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          has_mask=has_mask, bq=bq, bk=bk, nq=nq,
                          sub=_strip_width("dkv", bq)),
        grid=(bh, nk, nq),
        in_specs=[qspec_k_outer, kspec, kspec] + mask_spec * has_mask
                 + [qspec_k_outer, rowspec_k_outer, rowspec_k_outer],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
                   jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma)],
        scratch_shapes=[_VMEM((bk, d), jnp.float32),
                        _VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, *masks, do, lse, delta)
    return dq_t.transpose(0, 2, 1), dk, dv


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
    return dq, dk, dv, None if mask is None else jnp.zeros_like(mask)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = False,
                    scale: float | None = None, kv_mask=None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None):
    """Memory-efficient exact attention on model-layout tensors.

    Args:
      q: (B, Lq, H, D);  k, v: (B, Lk, H, D)  — same layout as
        `parallel.ring_attention.dense_attention` so the two are drop-in
        interchangeable inside models.  The MXU products take the operands
        in this dtype and accumulate in float32.
      causal: mask future positions (by absolute position, so Lq == Lk
        is expected when True).
      kv_mask: optional (B, Lk) key-validity mask (>0 == valid); without
        one the kernels take no mask operand.
      block_q / block_k: VMEM tile sizes, clamped to the (padded) sequence
        lengths; unset, `_choose_blocks` picks them from the shapes (under
        ``causal`` a key block no wider than the query block, so that
        future tiles are skipped).  The (bq × bk) f32 score tile must fit
        VMEM alongside the q/k/v blocks (`_vmem_need`).
      interpret: force Pallas interpret mode; default = auto (True off-TPU).

    Returns (B, Lq, H, D).  Rows with no valid key return 0 (same guard as
    ring_attention).
    """
    interpret = resolve_interpret(interpret)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    mask = None if kv_mask is None else kv_mask.astype(jnp.float32)

    if interpret and join_vma(q, k, v, mask):
        # inside shard_map on a non-TPU backend: Pallas's HLO interpreter
        # cannot currently lower under vma checking, so run the pure-jnp
        # kernel twin (identical math incl. NEG_INF/_TINY guards, and
        # differentiable by plain AD).  The real kernel covers TPU and
        # standalone-interpret tests; test_flash_block_primitives_match_
        # kernel ties the two together.
        if mask is None:
            mask = jnp.ones((b, lk), jnp.float32)
        out, _ = _fwd_block_ref(q, k, v, mask, scale, causal)
        return out

    bq, bk = _resolve_blocks(block_q, block_k, lq, lk, d, q.dtype.itemsize,
                             causal)
    if not interpret:  # the interpreter has no VMEM to budget
        _check_vmem_budget(bq, bk, d, q.dtype.itemsize)
    pad_q = (-lq) % bq
    pad_k = (-lk) % bk
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        if mask is None:
            mask = jnp.ones((b, lk), jnp.float32)
        mask = jnp.pad(mask, ((0, 0), (0, pad_k)))  # padded keys invalid (0)
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))

    # (B·H, 1, Lk): row b·H+h ← batch b; middle singleton for TPU tiling
    mask_bh = None if mask is None else jnp.repeat(mask, h, axis=0)[:, None]
    out = _flash_core(_to_bh(q), _to_bh(k), _to_bh(v), mask_bh,
                      scale, causal, bq, bk, interpret)
    out = _from_bh(out, b, h)
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
    _check_vmem_budget(bq, bk, d, q.dtype.itemsize)
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
    _check_vmem_budget(bq, bk, d, 4)       # cast to float32 below
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
