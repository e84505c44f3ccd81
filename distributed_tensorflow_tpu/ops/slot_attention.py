"""The one-token decode step over a monolithic slot table, as one Pallas
TPU kernel that reads only the prefix the round needs.

``serving/kv_cache.SlotKVCache`` keeps GPT-2's keys and values as
``(slots, max_len, heads, head_dim)`` leaves.  Each decode round puts one
new K and V row into every slot and attends the slot's query against its
rows.  The plain form (``slot_attention_plain``, the form until PR 38) does
that with ``select_slot_row`` and a length-masked dense attention over the
WHOLE table: every round reads and rewrites all ``max_len`` positions of
all slots, 12 GB a round at the serve cells' widths, though the median
round's longest stream holds a fifth of them or less.

THE RULE.  ``P`` is ``length + 1`` of the longest slot the round advances
(``advancing``; without it, every slot whose position lies inside the
table), rounded up to a block of ``prefix_part(max_len)`` positions (an
eighth of ``max_len``).  The kernel reads ``table[:, :P]`` and nothing
past it, and writes each slot's new row where it lies below ``P``.  A slot
that does not advance and stands at or past ``P`` (a slot frozen at
``max_len`` among them) writes nothing: its rows past its own length are
invalid anyway (``select_slot_row``'s drop rule).  Positions at or past
``P`` were masked for every advancing slot and added exactly 0, so only
the order of the sums (an online softmax in float32, block by block) can
differ from the plain form in the last bits.

WHAT ONE GRID STEP DOES.  The grid is ``(slot tiles, blocks)``; the
block axis runs innermost and in order, so the online-softmax state of a
tile (``m``, ``l`` and a lane-wide ``acc``, reduced over lanes once, at the
last block) stays in VMEM scratch.  The table enters as its transpose
``(slots, heads, head_dim, max_len)``, which is the order the v5e keeps
the leaf in (positions on the 128 lanes, head_dim on the sublanes): the
transpose is a bitcast there and no leaf is copied
(tests/test_tpu_compile.py holds that).  The K and V blocks' index maps
clamp the block index to ``P / part - 1``: a step past the prefix fetches
nothing new and computes nothing, so one compiled program serves every
round (the block count rides in as a scalar-prefetch operand).  The
scores are a sublane reduction of ``q * k`` over head_dim; the context
accumulates ``p * v`` lane-wide.  The query and the new rows arrive as the
model has them, ``(slots, 1, heads * head_dim)``, and are turned to run
along the lanes in VMEM at a tile's first step (an operand shaped
``(.., head_dim, 1)`` is padded 128 times in HBM: 3.1 ms a round of
copies on the chip).  The tile's slots are a loop, not written out, and
the ``pallas_call`` sits under one inlined ``jax.jit``: the 36 layers of a
step share one trace of the kernel.

THE WRITE.  The table leaves are also outputs, aliased to the inputs and
left in HBM (``pl.ANY``): the step that holds a slot's row puts it into the
block in VMEM and copies that one block (``heads * head_dim * part``
values) back with a DMA, which overlaps the tile's later blocks and is
waited at its last step.  Nothing else of the table is written: a round
writes ``slots`` blocks a leaf, where the plain form rewrote the table.

A Mosaic kernel cannot be partitioned by GSPMD, so a served module with a
mesh (``slot_mesh``) or tensor-parallel heads takes the plain form, as do
grouped-query heads and a block that is not a whole number of lanes
(``takes_kernel``).  Off the TPU the plain form is what runs, so every CPU
test of the serving path keeps its exact numbers; the kernel runs in the
Pallas interpreter where a test asks for it (``interpret=True``,
tests/test_serving.py)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_tpu.ops.flash_attention import resolve_interpret
from distributed_tensorflow_tpu.parallel.ring_attention import (
    NEG_INF, dense_attention)

PREFIX_PARTS = 8    # the step reads a prefix of eighths of max_len
LANES = 128
SLOT_TILE = 8       # slots a grid step
VMEM_LIMIT = 64 * 2**20     # a tile's K and V blocks (double-buffered),
                            # its rows along the lanes and its lane-wide
                            # accumulator: 26 MB at the serve cells' widths


def prefix_part(max_len: int) -> int:
    """The positions of one block of the prefix: an eighth of ``max_len``,
    or the whole table where 8 does not divide it."""
    return max_len // PREFIX_PARTS if max_len % PREFIX_PARTS == 0 \
        else max_len


def prefix_rows(lengths, advancing, max_len: int) -> int:
    """The host's twin of THE RULE (``SlotKVCache`` puts it on the
    ``decode_step`` span as ``rows_read``): ``length + 1`` of the longest
    advancing slot, rounded up to a block of ``prefix_part``."""
    need = max((int(n) + 1 for n, a in zip(lengths, advancing) if a),
               default=1)
    part = prefix_part(max_len)
    return min(-(-need // part) * part, max_len)


def select_slot_row(table, update, pos):
    """``table[b, p] = update[b, 0] if p == pos[b, 0] else table[b, p]``:
    how the plain form puts a slot's new K or V row into the table.

    ``table`` is ``(slots, max_len, ...)``, ``update`` ``(slots, 1, ...)``
    (cast here to the table's dtype), ``pos`` ``(slots, 1)``.  A select
    over the position axis and no scatter: the compiler fuses it into the
    pass the attention that follows makes over the table anyway (the
    scores' fusion emits the updated key leaf as a second output, the
    context's the value leaf), so the donated table is read once and
    written once, in the layout it arrived in.  The kernel above does the
    same for the prefix ``[:, :P]`` and writes back only the block that
    holds the row.

    DROP RULE: a position outside ``[0, max_len)`` equals no index, so the
    table is left as it was: a freed slot frozen at ``max_len``, a pad
    row of a chunk bucket past the table, a negative position."""
    hit = jnp.arange(table.shape[1])[None, :] == pos
    hit = hit.reshape(hit.shape + (1,) * (table.ndim - 2))
    return jnp.where(hit, update.astype(table.dtype), table)


def slot_attention_plain(q, k, v, keys, values, pos, widen=lambda t: t):
    """The full-table form: ``select_slot_row`` into both leaves, then the
    attention of every slot over all ``max_len`` positions under its
    length mask.  Returns ``(keys, values, out)``."""
    idx = pos[:, 0]
    keys = select_slot_row(keys, k, pos)
    values = select_slot_row(values, v, pos)
    valid = (jnp.arange(keys.shape[1])[None, :]
             <= idx[:, None]).astype(q.dtype)
    out = dense_attention(q, widen(keys), widen(values), causal=False,
                          kv_mask=valid)
    return keys, values, out


def on_tpu() -> bool:
    """Whether the program is built for the TPU (a test that compiles for a
    described v5e from the CPU steers this)."""
    return jax.default_backend() == "tpu"


def takes_kernel(heads: int, kv_heads: int, head_dim: int, max_len: int, *,
                 plain: bool = False, interpret: bool = False) -> bool:
    """Whether the step runs the kernel: on the TPU (or in the interpreter
    where a test asks for it), for a module that is not ``plain``, with as
    many K/V heads as query heads and, for Mosaic, a block of whole
    128-lane tiles."""
    if plain or not (interpret or on_tpu()):
        return False
    return heads == kv_heads and (interpret or (
        prefix_part(max_len) % LANES == 0
        and head_dim % 8 == 0 and heads * head_dim % LANES == 0))


def _slot_tile(slots: int) -> int:
    return max(d for d in range(1, SLOT_TILE + 1) if slots % d == 0)


def _kernel(nblk_ref, pos_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
            o_ref, k_hbm, v_hbm, q_scr, kn_scr, vn_scr, m_scr, l_scr,
            acc_scr, sem, *, tile, part, scale):
    """One (slot tile, block) grid step.  ``k_ref``/``v_ref`` blocks are
    ``(tile, heads, head_dim, part)``; ``q_ref``, ``kn_ref``, ``vn_ref`` and
    ``o_ref`` ``(tile, 1, heads * head_dim)``, rows as the model has them;
    ``k_hbm``/``v_hbm`` the whole transposed table leaves, aliased to the
    inputs."""
    t, j = pl.program_id(0), pl.program_id(1)
    n = nblk_ref[0]
    heads, head_dim = k_ref.shape[1:3]

    def each_slot(body):
        """``body(s)`` for every slot of the tile, in a loop that is not
        written out (the kernel's code, and its lowering, stay one slot's
        size)."""
        def step(s, carry):
            body(s)
            return carry
        jax.lax.fori_loop(0, tile, step, 0)

    def lanes(row):
        """``(1, heads * head_dim)`` -> ``(heads, head_dim, part)``: each
        value of the row along a whole row of lanes, as the table's block
        holds head_dim (one transpose of the broadcast row)."""
        wide = jnp.broadcast_to(row.astype(jnp.float32),
                                (part, row.shape[-1]))
        return wide.T.reshape(heads, head_dim, part)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        @each_slot
        def _(s):
            q_scr[s] = lanes(q_ref[s]) * scale
            kn_scr[s] = lanes(kn_ref[s]).astype(kn_scr.dtype)
            vn_scr[s] = lanes(vn_ref[s]).astype(vn_scr.dtype)

    def write(s, blk):
        """The DMAs of slot ``s``'s updated block ``blk`` of keys and
        values, from the rows' scratch (the new rows are not needed once
        their block is written) into the table."""
        window = (t * tile + s, slice(None), slice(None),
                  pl.ds(blk * part, part))
        return [pltpu.make_async_copy(src.at[s], dst.at[window],
                                      sem.at[i, s])
                for i, (src, dst) in enumerate(((kn_scr, k_hbm),
                                                (vn_scr, v_hbm)))]

    @pl.when(j < n)
    def _():
        lane = j * part + jax.lax.broadcasted_iota(jnp.int32, (1, 1, part),
                                                   2)

        @each_slot
        def _(s):
            p_s = pos_ref[t * tile + s]
            hit = lane == p_s
            kb = jnp.where(hit, kn_scr[s], k_ref[s])
            vb = jnp.where(hit, vn_scr[s], v_ref[s])

            @pl.when((p_s >= j * part) & (p_s < (j + 1) * part))
            def _():
                kn_scr[s] = kb
                vn_scr[s] = vb
                for c in write(s, j):
                    c.start()

            sc = jnp.sum(q_scr[s] * kb.astype(jnp.float32), axis=1,
                         keepdims=True)                  # (heads, 1, part)
            sc = jnp.where(lane <= p_s, sc, NEG_INF)
            m_prev = m_scr[s]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=2, keepdims=True))
            p = jnp.exp(sc - m_new)
            fade = jnp.exp(m_prev - m_new)
            m_scr[s] = m_new
            l_scr[s] = l_scr[s] * fade + jnp.sum(p, axis=2, keepdims=True)
            acc_scr[s] = acc_scr[s] * fade + p * vb.astype(jnp.float32)

    @pl.when(j == n - 1)
    def _():
        @each_slot
        def _(s):
            ctx = (acc_scr[s] / l_scr[s]).reshape(heads * head_dim, part)
            o_ref[s] = jnp.sum(ctx.T, axis=0,
                               keepdims=True).astype(o_ref.dtype)

    # the writes overlap the tile's later blocks: wait for them at its
    # last step, before the next tile's rows take the scratch
    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        @each_slot
        def _(s):
            p_s = pos_ref[t * tile + s]

            @pl.when((p_s >= 0) & (p_s < n * part))     # it was started
            def _():
                for c in write(s, p_s // part):
                    c.wait()


def table_block(tile: int, heads: int, head_dim: int, part: int):
    """The K and V blocks of grid step ``(t, j)``: slots ``t * tile ..``,
    positions ``j * part ..``, the block index held at the prefix's last
    block ``n[0] - 1`` past it, so that a step past the prefix fetches
    nothing (the pipeline copies a block in only when its index moves)."""
    return pl.BlockSpec((tile, heads, head_dim, part),
                        lambda t, j, n, p: (t, 0, 0,
                                            jnp.minimum(j, n[0] - 1)))


def slot_attention_kernel(q, k, v, keys, values, pos, advancing=None, *,
                          interpret: bool = False):
    """THE RULE's prefix, read and written by the kernel.  ``q``, ``k``,
    ``v`` are ``(slots, 1, heads, head_dim)``; ``keys``/``values``
    ``(slots, max_len, heads, head_dim)``, donated by the caller; ``pos``
    ``(slots, 1)``; ``advancing`` ``(slots,)`` bool or None.  Returns
    ``(keys, values, out)`` like ``slot_attention_plain``."""
    max_len = keys.shape[1]
    idx = pos[:, 0].astype(jnp.int32)
    live = (idx < max_len) if advancing is None else advancing
    need = jnp.max(jnp.where(live, idx + 1, 1))
    part = prefix_part(max_len)
    nblk = jnp.clip((need + part - 1) // part, 1, max_len // part)
    return _kernel_call(q, k, v, keys, values, idx, nblk[None],
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def _kernel_call(q, k, v, keys, values, idx, nblk, *, interpret: bool):
    """The ``pallas_call`` under one inlined ``jax.jit``: the layers of a
    step share one trace of the kernel (36 traces of its written-out
    body made the step's lowering 10 s longer on the host)."""
    slots, max_len, heads, head_dim = keys.shape
    part = prefix_part(max_len)
    tile = _slot_tile(slots)

    def rows(x):
        return x.reshape(slots, 1, heads * head_dim)

    def table(x):       # positions last: the v5e's own order of the leaf
        return x.transpose(0, 2, 3, 1)

    row = pl.BlockSpec((tile, 1, heads * head_dim),
                       lambda t, j, n, p: (t, 0, 0))
    block = table_block(tile, heads, head_dim, part)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kt, vt = table(keys), table(values)
    wide = (tile, heads, head_dim, part)
    out, kt, vt = pl.pallas_call(
        functools.partial(_kernel, tile=tile, part=part,
                          scale=head_dim ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots // tile, max_len // part),
            in_specs=[row, row, row, block, block],
            out_specs=[row, hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM(wide, jnp.float32),
                pltpu.VMEM(wide, keys.dtype),
                pltpu.VMEM(wide, values.dtype),
                pltpu.VMEM((tile, heads, 1, 1), jnp.float32),
                pltpu.VMEM((tile, heads, 1, 1), jnp.float32),
                pltpu.VMEM(wide, jnp.float32),
                pltpu.SemaphoreType.DMA((2, tile)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((slots, 1, heads * head_dim),
                                        q.dtype),
                   jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        input_output_aliases={5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret and resolve_interpret(True),
    )(nblk.astype(jnp.int32), idx, rows(q), rows(k), rows(v), kt, vt)
    back = (0, 3, 1, 2)
    return (kt.transpose(back), vt.transpose(back),
            out.reshape(slots, 1, heads, head_dim))


def slot_decode_attention(q, k, v, keys, values, pos, advancing=None, *,
                          widen=lambda t: t, plain: bool = False,
                          interpret: bool = False):
    """The one-token step's attention over the slot table: the kernel on
    the TPU where it fits, the plain form elsewhere (off the TPU unless a
    test asks for the interpreter, ``plain`` for a module the kernel cannot
    serve: a mesh or tensor-parallel heads).  Returns
    ``(keys, values, out)``."""
    if not takes_kernel(q.shape[2], keys.shape[2], keys.shape[3],
                        keys.shape[1], plain=plain, interpret=interpret):
        return slot_attention_plain(q, k, v, keys, values, pos, widen)
    return slot_attention_kernel(q, k, v, keys, values, pos, advancing,
                                 interpret=interpret)
