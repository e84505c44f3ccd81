"""Paged decode attention as a Pallas TPU kernel (vLLM's PagedAttention).

The serving KV table in ``serving/kv_cache.py`` historically stored one
contiguous ``(slots, max_len)`` row per slot, and the decode step ran a
full-width gather + softmax over it.  The paged layout (Kwon et al.,
arXiv:2309.06180) breaks that row into fixed-size physical blocks in one
shared pool ``(num_blocks, kv_heads, block, head_dim)`` and gives each slot
an int32 *block table*; a prefix-cache hit then aliases pool blocks by
pointer instead of copying KV bytes.  This kernel is the read side of that
design: a decode/verify attention kernel that follows the block table
**inside** the kernel, so the gathered ``(slots, max_len)`` K/V copy never
materializes in HBM.

The pool keeps the kv head AHEAD of the token axis so that one (block,
head) window is the array's full last two dimensions ``(block, head_dim)``:
Mosaic refuses a block whose last two dimensions are neither the array's
own nor a multiple of the (8, 128) tile, which a one-head window of a
``(.., block, kv_heads, head_dim)`` pool is whenever ``kv_heads > 1``.

Grid ``(slots, kv_heads, max_blocks)`` — the block axis iterates innermost
and sequentially, which is what lets the online-softmax accumulators
(m/l/acc) persist in VMEM scratch across a slot's blocks (the same pattern
as ``_fwd_kernel`` in flash_attention.py).  The block table and per-slot
positions ride in as *scalar-prefetch* operands
(``pltpu.PrefetchScalarGridSpec``): each K/V BlockSpec's index_map reads
``bt[s, j]`` to window the pool block-indirectly, the Pallas analogue of
vLLM's physical-block lookup.

Queries are ``(slots, l_q, heads, head_dim)`` — ``l_q == 1`` is the decode
step and ``l_q == k+1`` the speculative ``verify_block`` variant; each
query row is masked to keys at or before its own position
(``t <= pos + row % l_q``).  Grouped-query attention folds the query-head
group into the row axis, so the kernel always sees one kv head per grid
step.  int8 KV composes in-kernel: the per-vector scales of a block are a
lane-major ``(1, block)`` row, applied to the ``(rows, block)`` score and
probability tiles (``(q·k_int)·s == q·(k_int·s)``), so the dequantized
f32 block the unfused path pays for never exists.

A Mosaic kernel cannot be partitioned by GSPMD ("Mosaic kernels cannot
be automatically partitioned. Please wrap the call in a shard_map", chip
run, PR 21), so under a serving mesh the caller passes the mesh and the
call runs in ``jax.shard_map`` over its 'data' axis: queries, tables and
positions by slot, the pools whole on every device (they replicate there).

On non-TPU backends the kernel runs in Pallas interpret mode (the
flash_attention precedent), so CPU CI exercises the real kernel, not a
shadow implementation.  ``paged_attention_reference`` is the pure-jnp twin:
the gather + dense-softmax oracle used for parity tests and as the
fallback when operands carry varying axes under ``jax.shard_map`` on CPU
(interpret mode cannot lower pallas_call under vma checking).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_tpu.ops.flash_attention import (
    join_vma, resolve_interpret)
from distributed_tensorflow_tpu.parallel.mesh import DATA_AXIS

NEG_INF = -1e30  # matches parallel.ring_attention.NEG_INF
_TINY = 1e-30


def _kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
            nb, blk, l_q, sm_scale, quantized):
    """One (slot, kv_head, block) grid step of the online softmax.

    ``q_ref`` block is (1, 1, GL, D) — GL = group × l_q query rows for this
    kv head; ``k_ref``/``v_ref`` blocks are (1, 1, blk, D) pool blocks
    windowed through ``bt_ref[s, j]``.  When ``quantized``, ``rest`` leads
    with the (1, kv_heads, blk) per-vector scale blocks of that pool block.
    """
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    s, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    p0 = pos_ref[s]  # first query's position for this slot

    def compute():
        qb = q_ref[0, 0].astype(jnp.float32)          # (GL, D)
        kb = k_ref[0, 0].astype(jnp.float32)          # (blk, D)
        vb = v_ref[0, 0].astype(jnp.float32)
        sc = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sc = sc * sm_scale
        if quantized:  # in-kernel dequant: this head's (1, blk) scale row
            sc = sc * ks_ref[0, pl.ds(h, 1), :]
        # key position t vs each query row's own position (row % l_q walks
        # the verify block; the group axis repeats the same position)
        t = j * blk + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        qoff = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0) % l_q
        sc = jnp.where(t <= p0 + qoff, sc, NEG_INF)

        m_prev, l_prev = m_scr[:], l_scr[:]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        if quantized:
            p = p * vs_ref[0, pl.ds(h, 1), :]
        acc_scr[:] = acc_scr[:] * corr + jnp.dot(
            p, vb, preferred_element_type=jnp.float32)

    # skip blocks entirely past the last query's position (dead keys)
    pl.when(j * blk <= p0 + l_q - 1)(compute)

    @pl.when(j == nb - 1)
    def _():
        o_ref[0, 0] = (acc_scr[:]
                       / jnp.maximum(l_scr[:], _TINY)).astype(o_ref.dtype)


def _fold_gqa(q, kv_heads):
    """(S, L, H, D) → (S, KVH, G·L, D): group rides the query-row axis."""
    s, l, h, d = q.shape
    g = h // kv_heads
    return (q.reshape(s, l, kv_heads, g, d)
            .transpose(0, 2, 3, 1, 4).reshape(s, kv_heads, g * l, d))


def _unfold_gqa(out, l_q, heads):
    s, kvh, gl, d = out.shape
    g = gl // l_q
    return (out.reshape(s, kvh, g, l_q, d)
            .transpose(0, 3, 1, 2, 4).reshape(s, l_q, heads, d))


def gather_pool(pool, block_tables):
    """Logical view of a pool leaf through the block tables: payload
    ``(N, KVH, blk, D)`` → ``(S, MB·blk, KVH, D)``, scales ``(N, KVH, blk)``
    → ``(S, MB·blk, KVH)`` — the monolithic table's axis order, so the
    gather read path shares the dense math of the monolithic cache."""
    g = jnp.take(pool, block_tables, axis=0)      # (S, MB, KVH, blk[, D])
    g = jnp.swapaxes(g, 2, 3)                     # (S, MB, blk, KVH[, D])
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def paged_attention_reference(q, k_pool, v_pool, block_tables, positions, *,
                              k_scale=None, v_scale=None, scale=None):
    """Pure-jnp oracle: gather the pool through the block table, dequant,
    widen kv heads, dense masked softmax.  Same signature as the kernel
    entry; the parity tests pin the kernel against this."""
    s, l_q, h, d = q.shape
    kvh = k_pool.shape[1]
    keys = gather_pool(k_pool, block_tables)
    vals = gather_pool(v_pool, block_tables)
    if k_scale is not None:
        keys = (keys.astype(jnp.float32)
                * gather_pool(k_scale, block_tables)[..., None])
        vals = (vals.astype(jnp.float32)
                * gather_pool(v_scale, block_tables)[..., None])
    if kvh != h:
        keys = jnp.repeat(keys, h // kvh, axis=2)
        vals = jnp.repeat(vals, h // kvh, axis=2)
    from distributed_tensorflow_tpu.parallel.ring_attention import (
        dense_attention)
    t = jnp.arange(keys.shape[1], dtype=jnp.int32)
    valid = (t[None, None, :]
             <= positions[:, None, None]
             + jnp.arange(l_q, dtype=jnp.int32)[None, :, None])
    out = dense_attention(q.astype(jnp.float32), keys.astype(jnp.float32),
                          vals.astype(jnp.float32), causal=False,
                          scale=scale, kv_mask=valid)
    return out.astype(q.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, positions, *,
                    k_scale=None, v_scale=None, scale=None,
                    interpret=None, mesh=None):
    """Fused paged decode attention.

    Args:
      q: (slots, l_q, heads, head_dim) queries — model layout; ``l_q`` is 1
        for the decode step, ``k+1`` for speculative verify.
      k_pool, v_pool: (num_blocks, kv_heads, block, head_dim) physical
        block pools (f32/bf16, or int8 with scales).
      block_tables: (slots, max_blocks) int32 — pool block id per logical
        block.  Unmapped entries must hold a valid index (0 is fine): the
        length mask kills their scores, but the windowed load still reads.
      positions: (slots,) int32 — position of each slot's FIRST query row
        (its current length); query row r attends keys ``t <= pos + r``.
      k_scale, v_scale: (num_blocks, kv_heads, block) f32 per-vector
        scales, required iff the pools are int8 (in-kernel dequant).
      scale: softmax scale; defaults to ``head_dim ** -0.5``.
      interpret: Pallas interpret mode; defaults to True off-TPU.
      mesh: the serving mesh when the surrounding program spans more than
        one device: the call then runs in ``jax.shard_map`` over the
        mesh's 'data' axis, slots sharded, pools replicated.

    Returns (slots, l_q, heads, head_dim) in ``q.dtype``.
    """
    if mesh is not None and mesh.shape.get(DATA_AXIS, 1) > 1:
        from jax.sharding import PartitionSpec as P

        by_slot, whole = P(DATA_AXIS), P()
        scales = () if k_scale is None else (k_scale, v_scale)

        def local(q, k_pool, v_pool, block_tables, positions, *scales):
            # the replicated operands join the slot-varying ones: the
            # pallas_call sees one set of varying axes (a runtime no-op)
            k_pool, v_pool, *scales = (
                jax.lax.pcast(x, DATA_AXIS, to="varying")
                for x in (k_pool, v_pool, *scales))
            ks, vs = scales or (None, None)
            return paged_attention(
                q, k_pool, v_pool, block_tables, positions, k_scale=ks,
                v_scale=vs, scale=scale, interpret=interpret)

        return jax.shard_map(
            local, mesh=mesh, axis_names={DATA_AXIS},
            in_specs=(by_slot, whole, whole, by_slot, by_slot)
            + (whole,) * len(scales),
            out_specs=by_slot,
        )(q, k_pool, v_pool, block_tables, positions, *scales)
    s, l_q, h, d = q.shape
    n, kvh, blk, _ = k_pool.shape
    mb = block_tables.shape[1]
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be given together")
    if h % kvh:
        raise ValueError(f"heads={h} not divisible by kv_heads={kvh}")
    interpret = resolve_interpret(interpret)
    if interpret and join_vma(q, k_pool, v_pool, k_scale, v_scale):
        # shard_map-on-CPU: interpret mode cannot lower under vma
        # checking — fall back to the jnp twin (flash_attention precedent)
        return paged_attention_reference(
            q, k_pool, v_pool, block_tables, positions,
            k_scale=k_scale, v_scale=v_scale, scale=scale)
    sm_scale = scale if scale is not None else d ** -0.5
    gl = (h // kvh) * l_q
    qf = _fold_gqa(q, kvh)
    bt = block_tables.astype(jnp.int32)
    pos = positions.astype(jnp.int32)

    kernel = functools.partial(_kernel, nb=mb, blk=blk, l_q=l_q,
                               sm_scale=sm_scale, quantized=quantized)
    qspec = pl.BlockSpec((1, 1, gl, d), lambda s, h, j, bt, pos: (s, h, 0, 0))
    kvspec = pl.BlockSpec((1, 1, blk, d),
                          lambda s, h, j, bt, pos: (bt[s, j], h, 0, 0))
    in_specs = [qspec, kvspec, kvspec]
    operands = [qf, k_pool, v_pool]
    if quantized:
        # all heads' scales of the block: (kv_heads, blk) is the array's
        # full last two dimensions, the kernel picks its head's row
        sspec = pl.BlockSpec((1, kvh, blk),
                             lambda s, h, j, bt, pos: (bt[s, j], 0, 0))
        in_specs += [sspec, sspec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, kvh, mb),
        in_specs=in_specs,
        out_specs=qspec,
        scratch_shapes=[
            pltpu.VMEM((gl, 1), jnp.float32),
            pltpu.VMEM((gl, 1), jnp.float32),
            pltpu.VMEM((gl, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (s, kvh, gl, d), q.dtype,
            vma=join_vma(qf, k_pool, v_pool, k_scale, v_scale)),
        interpret=interpret,
    )(bt, pos, *operands)
    return _unfold_gqa(out, l_q, h)
