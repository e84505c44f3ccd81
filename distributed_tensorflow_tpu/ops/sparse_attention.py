"""DeepSeek-style sparse attention as Pallas TPU kernels: a learned indexer
scores every earlier key of a query, and the query attends to the ``top_k``
keys of highest score only.

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])      (s <= t)
    S_t     = the top_k positions s <= t by I[t, s], ties to the lower
              position (as ``lax.top_k`` breaks them); every s <= t while
              t < top_k
    o[t, h] = softmax_{s in S_t}(q[t, h] . k[s, g(h)] * scale) v[s, g(h)]

``qI`` (T, Hi, Di) and ``kI`` (S, Di) are bfloat16 and their products
accumulate in float32; ``w`` (T, Hi) is float32 and carries every constant
factor of the score (the caller folds them in).  A score of zero counts as
one value whatever its sign bit (``order_key``).

THE SELECTION IS EXACT.  A score is turned into an int32 whose order is the
float's (``order_key``); the ``top_k``-th largest of a query's row is found
by bisection over the 32 bits, each step a count of the keys at or above the
midpoint, which needs no sort and no gather.  What the bisection finds is a
threshold ``thr`` and a position ``cut``: a key is selected when it lies
above ``thr``, or at ``thr`` and at or before ``cut`` (the ties at the
threshold are taken from the lowest position up, as many as the top_k still
needs).  A row whose count lands on ``top_k`` exactly stops early.  A count
is a compare and an add an element (``_count``): each key tile's hits go as
0 or 1 into a (rows, 128) accumulator held in registers, summed over lanes
once a count, and only the tile on the block's diagonal is masked by
position, since every earlier tile lies wholly at or before every row.

Three kernels, each a ``pallas_call`` with a ``name`` the device trace finds:

* ``dsa_prefill`` (a block of a prompt from position 0).  The grid is
  ``(query blocks, 2, key tiles)``.  Phase 0 scores the block's queries
  against every key tile up to the block's last query and keeps the scores'
  order keys in VMEM (``(tiles, Q_BLOCK, K_BLOCK)`` int32: 32 MB at 65,536
  positions); at the first step of phase 1 the bisection selects; phase 1
  is a flash attention over the K/V tiles whose mask is the selection.  A
  tile past the block's last query fetches nothing (its block index is
  held) and computes nothing, and a tile in which no query of the block
  selects a key is skipped.  Phase 1 holds its tiles keys down and queries
  across, as the training flash kernels do: at the first step of phase 1
  each K/V head's whole group of queries is laid across the lanes,
  ``(D, group x Q_BLOCK)`` head by head, so a K/V tile is multiplied once
  a group (``STRIP`` lanes a piece), the running max and sum of a query
  are lane rows that reduce down sublanes, and the selection's mask is
  turned to ``(K_BLOCK, Q_BLOCK)`` once a tile and serves every head.  At
  the block's last tile each head's ``(D, Q_BLOCK)`` is turned back into
  the output's ``(heads, positions, D)``.
* ``dsa_step_index`` (the one-token step's scoring).  The grid is ``(slots,
  index tiles)``; a slot's tiles past its own position fetch and compute
  nothing, so a round reads each slot's index rows to its length and not to
  ``max_len``.  It also writes the round's new index key: the tile that
  holds a slot's write position is scored with the key in it and copied
  back into the table, which the kernel holds in place (aliased), so no
  other program touches the index rows in the step.  The step takes the
  ``top_k`` of those scores with ``lax.top_k`` (exact, and its ties are the
  rule's by definition) and gathers the selected K/V rows.
* ``dsa_step_attend`` (the one-token step's attention): each slot's query
  over its gathered rows only, the rows past the slot's count masked.

Off the TPU the plain forms (``prefill_plain``, ``step_index_plain``,
``step_attend_plain``) are what the models run, so every CPU test of the
serving path is quick; the kernels run in the Pallas interpreter where a
test asks for them (``interpret=True``)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_tpu.ops.flash_attention import (
    _TN, resolve_interpret)

Q_BLOCK = 128           # prefill queries a grid step
K_BLOCK = 512           # prefill keys a grid step
STEP_BLOCK = 2048       # index rows a grid step of the step's scoring
UNROLL = 4              # key tiles a step of the selection's counting loop
LANES = 128             # lanes of the selection's hit accumulator
STRIP = 256             # lanes of a group's queries a piece of phase 1 takes
NEG = -1e30             # a score no selected key has
INT_MIN, INT_MAX = -(2 ** 31), 2 ** 31 - 1
# the prefill's order keys (32 MB at 65,536 positions), its blocks and the
# score tiles of 16 indexer heads and 32 query heads
VMEM_LIMIT = 100 * 2 ** 20


def on_tpu() -> bool:
    """Whether the program is built for the TPU (a test that compiles for a
    described v5e from the CPU steers this)."""
    return jax.default_backend() == "tpu"


def order_key(score):
    """float32 -> int32 with the float's order (-0.0 taken as 0.0)."""
    bits = lax.bitcast_convert_type(jnp.where(score == 0, 0.0, score),
                                    jnp.int32)
    return bits ^ ((bits >> 31) & INT_MAX)


# ------------------------------------------------------------ plain forms

def index_scores(qi, w, ki):
    """``qi`` (..., T, Hi, Di), ``w`` (..., T, Hi), ``ki`` (..., S, Di) ->
    ``I`` (..., T, S) float32."""
    r = jnp.einsum("...thd,...sd->...ths", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("...ths,...th->...ts", jax.nn.relu(r),
                      w.astype(jnp.float32))


def select_plain(scores, upto, top_k: int):
    """The selection as a mask: ``scores`` (..., T, S), ``upto`` (..., T)
    the last position each query may select -> (..., T, S) bool."""
    s = scores.shape[-1]
    valid = jnp.arange(s) <= upto[..., None]
    masked = jnp.where(valid, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    _, idx = lax.top_k(masked, min(top_k, s))
    chosen = jnp.any(idx[..., None] == jnp.arange(s), axis=-2)
    return chosen & valid


def _attend_masked(q, k, v, mask, scale):
    """``q`` (..., T, Hq, D), ``k``/``v`` (..., S, Hk, D), ``mask`` (..., T,
    S) -> (..., T, Hq, D) in ``v``'s dtype."""
    g = q.shape[-2] // k.shape[-2]
    qg = q.reshape(q.shape[:-2] + (k.shape[-2], g, q.shape[-1]))
    s = jnp.einsum("...thgd,...shd->...hgts", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[..., None, None, :, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("...hgts,...shd->...thgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(q.shape).astype(v.dtype)


def prefill_plain(q, k, v, qi, w, ki, *, top_k: int, scale: float):
    """What ``dsa_prefill`` computes, in jnp: ``q`` (B, T, Hq, D), ``k``,
    ``v`` (B, T, Hk, D), ``qi`` (B, T, Hi, Di), ``w`` (B, T, Hi), ``ki``
    (B, T, Di)."""
    t = q.shape[1]
    mask = select_plain(index_scores(qi, w, ki),
                        jnp.broadcast_to(jnp.arange(t), q.shape[:2]), top_k)
    return _attend_masked(q, k, v, mask, scale)


def step_index_plain(qi, w, index_keys, new_key, pos, put):
    """What ``dsa_step_index`` computes: ``qi`` (slots, Hi, Di), ``w``
    (slots, Hi), ``index_keys`` (slots, Di, max_len), ``new_key`` (slots,
    Di) written at ``put`` (slots,; a position past the table writes
    nothing), ``pos`` (slots,) -> the scores (slots, max_len) float32,
    meaningful at positions ``<= pos``, and the index rows."""
    del pos
    index_keys = index_keys.at[jnp.arange(qi.shape[0]), :, put].set(
        new_key.astype(index_keys.dtype), mode="drop")
    r = jnp.einsum("bhd,bdl->bhl", qi, index_keys,
                   preferred_element_type=jnp.float32)
    return (jnp.einsum("bhl,bh->bl", jax.nn.relu(r), w.astype(jnp.float32)),
            index_keys)


def step_attend_plain(q, keys, values, count, *, scale: float):
    """What ``dsa_step_attend`` computes: ``q`` (slots, Hq, D), ``keys``,
    ``values`` (slots, K, Hk * D) the gathered rows, ``count`` (slots,) how
    many of them are real -> (slots, Hq, D)."""
    slots, hq, d = q.shape
    hk = keys.shape[-1] // d
    mask = jnp.arange(keys.shape[1])[None, None, :] < count[:, None, None]
    return _attend_masked(q[:, None], keys.reshape(slots, -1, hk, d),
                          values.reshape(slots, -1, hk, d), mask,
                          scale)[:, 0]


# ----------------------------------------------------------- the selection

def step_select(scores, pos, top_k: int):
    """The step's selection: ``scores`` (slots, max_len) of
    ``dsa_step_index`` or its plain form, ``pos`` (slots,) -> the indices
    of the top_k rows at or before ``pos`` (slots, K) and how many of them
    are real (slots,)."""
    k = min(top_k, scores.shape[1])
    valid = jnp.arange(scores.shape[1])[None, :] <= pos[:, None]
    masked = jnp.where(valid, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    _, idx = lax.top_k(masked, k)
    return idx, jnp.minimum(pos + 1, k)


# ------------------------------------------------------------ the prefill

def _tile_keys(qi, w, ki):
    """Order keys of one query block's scores against one key tile:
    ``qi`` (Hi, bq, Di) bf16, ``w`` (bq, Hi) f32, ``ki`` (Di, bk) bf16 ->
    (bq, bk) int32.  The heads are summed in order, 0 first."""
    hi, bq, di = qi.shape
    r = jnp.dot(qi.reshape(hi * bq, di), ki,
                preferred_element_type=jnp.float32)
    s = w[:, 0:1] * jnp.maximum(r[:bq], 0.0)
    for h in range(1, hi):
        s = s + w[:, h:h + 1] * jnp.maximum(r[h * bq:(h + 1) * bq], 0.0)
    return order_key(s)


def _count(keys, last, upto, pred, bound, *, every_tile=False):
    """Over key tiles ``0 .. last`` of ``keys`` (tiles, bq, bk), how many
    positions ``s <= upto`` (bq, 1) of each row hold an order key ``k``
    with ``pred(k, bound)`` (``bound`` (bq, 1)): (bq, 1) int32.

    A tile's hits are added as 0 or 1, 128 lanes at a time, into a
    (bq, 128) accumulator held in registers: a compare and an add an
    element, and one sum over lanes a count.  Every position of a tile
    before ``last`` lies at or before every query of the block (a block
    starts at a multiple of ``bq``, a tile at one of ``bk``, and ``bq``
    divides ``bk``), so only tile ``last`` is held to ``upto``, unless
    ``every_tile`` asks for a bound below the block's positions."""
    _, bq, bk = keys.shape
    w = LANES if bk % LANES == 0 else bk
    wide = jnp.broadcast_to(bound, (bq, w))
    lane = lax.broadcasted_iota(jnp.int32, (bq, w), 1)

    def add(jj, acc, masked):
        for c in range(0, bk, w):
            hit = pred(keys[jj, :, pl.ds(c, w)], wide)
            if masked:
                hit = hit & (jj * bk + c + lane <= upto)
            acc = acc + hit.astype(jnp.int32)
        return acc

    acc = jnp.zeros((bq, w), jnp.int32)
    if every_tile:
        acc = lax.fori_loop(0, last + 1,
                            lambda jj, a: add(jj, a, True), acc)
    else:
        def unrolled(u, a):
            for v in range(UNROLL):
                a = add(u * UNROLL + v, a, False)
            return a

        whole = last // UNROLL
        acc = lax.fori_loop(0, whole, unrolled, acc)
        acc = lax.fori_loop(whole * UNROLL, last,
                            lambda jj, a: add(jj, a, False), acc)
        acc = add(last, acc, True)
    return jnp.sum(acc, axis=1, keepdims=True)


def _any(flag, rows):
    """Whether any entry of the (bq, 1) bool ``rows`` is set, as a scalar
    (through the SMEM ``flag``)."""
    flag[0] = jnp.max(rows.astype(jnp.int32))
    return flag[0] > 0


def _select(keys, thr, cut, flag, t_pos, last, top_k: int):
    """THE SELECTION of a query block: ``thr`` and ``cut`` (bq, 1) int32
    from the order keys of tiles ``0 .. last``."""
    bq = t_pos.shape[0]
    everything = t_pos + 1 <= top_k
    count = functools.partial(_count, keys, last, t_pos)

    # bisection: count(key >= lo) >= top_k > count(key >= hi) until hi =
    # lo + 1; a row whose count lands on top_k exactly is done at once
    # (lo = mid - 1, hi = mid: the keys above lo are its top_k).  ``hi - 1
    # > lo`` is how "apart by more than one" is asked without overflow.
    def step(carry):
        it, lo, hi, _ = carry
        mid = (lo >> 1) + (hi >> 1) + (lo & hi & 1)
        c = count(jnp.greater_equal, mid)
        live = hi - 1 > lo
        up = live & (c >= top_k)
        exact = live & (c == top_k)
        lo = jnp.where(exact, mid - 1, jnp.where(up, mid, lo))
        hi = jnp.where(up & ~exact, hi, jnp.where(live, mid, hi))
        return it + 1, lo, hi, _any(flag, hi - 1 > lo)

    lo = jnp.where(everything, INT_MAX - 1, INT_MIN)
    hi = jnp.full((bq, 1), INT_MAX, jnp.int32)
    _, lo, _, _ = lax.while_loop(lambda c: (c[0] < 33) & c[3], step,
                                 (jnp.int32(0), lo, hi,
                                  _any(flag, hi - 1 > lo)))
    thr[...] = jnp.where(everything, INT_MIN, lo)

    # the ties at thr, lowest position first: the m-th of them is the cut
    # (m = 0: none of them; all of them fit: every one)
    m = top_k - count(jnp.greater, thr[...])
    ties = count(jnp.equal, thr[...])
    cut[...] = jnp.where(~everything & (m <= 0), -1, INT_MAX)
    need = ~everything & (m > 0) & (ties > m)

    @pl.when(_any(flag, need))
    def _():
        # the m-th tie lies at or before t: a bound past t changes nothing
        def halve(_, carry):
            lo_p, hi_p = carry
            mid = (lo_p + hi_p) >> 1
            ok = _count(keys, last, mid, jnp.equal, thr[...],
                        every_tile=True) >= m
            return jnp.where(ok, lo_p, mid), jnp.where(ok, mid, hi_p)

        span = (last + 1) * keys.shape[2]
        bits = max(1, int(keys.shape[0] * keys.shape[2]).bit_length())
        _, hi_p = lax.fori_loop(0, bits, halve,
                                (jnp.full((bq, 1), -1, jnp.int32),
                                 jnp.full((bq, 1), span - 1, jnp.int32)))
        cut[...] = jnp.where(need, hi_p, cut[...])


def _prefill_kernel(w_ref, qi_ref, ki_ref, q_ref, k_ref, v_ref, o_ref,
                    keys, thr, cut, flag, qt_scr, m_scr, l_scr, acc_scr, *,
                    bq: int, bk: int, top_k: int, scale: float, strip: int):
    i, phase, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last = (i * bq + bq - 1) // bk
    t_pos = i * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    hq, hk = q_ref.shape[0], k_ref.shape[0]
    group = hq // hk

    @pl.when((phase == 0) & (j <= last))
    def _():
        keys[j] = _tile_keys(qi_ref[...], w_ref[...], ki_ref[...])

    @pl.when((phase == 1) & (j == 0))
    def _():
        _select(keys, thr, cut, flag, t_pos, last, top_k)
        # each K/V head's group of queries across the lanes, head-major:
        # (d, group * bq), laid once a block
        for h in range(hq):
            g, c = divmod(h, group)
            qt_scr[g, :, pl.ds(c * bq, bq)] = q_ref[h].T
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((phase == 1) & (j <= last))
    def _():
        s_pos = j * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        tile = keys[j]
        sel = (((tile > thr[...]) | ((tile == thr[...]) & (s_pos <= cut[...])))
               & (s_pos <= t_pos))

        @pl.when(_any(flag, jnp.max(sel.astype(jnp.int32), axis=1,
                                    keepdims=True) > 0))
        def _():
            # keys down, queries across: one head's mask, for every head,
            # as a bias (a score plus NEG is NEG: no score reaches its
            # last bit)
            bias = jnp.where(sel, 0.0, NEG).T
            bias = jnp.concatenate([bias] * (strip // bq), axis=1)
            for g in range(hk):
                k, v = k_ref[g], v_ref[g]
                for c in range(0, group * bq, strip):
                    cols = pl.ds(c, strip)
                    s = jnp.dot(k, qt_scr[g, :, cols],
                                preferred_element_type=jnp.float32)
                    s = s * scale + bias
                    m_prev = m_scr[g, :, cols]
                    m_new = jnp.maximum(m_prev,
                                        jnp.max(s, axis=0, keepdims=True))
                    p = jnp.exp(s - m_new)
                    fade = jnp.exp(m_prev - m_new)
                    m_scr[g, :, cols] = m_new
                    l_scr[g, :, cols] = l_scr[g, :, cols] * fade + jnp.sum(
                        p, axis=0, keepdims=True)
                    acc_scr[g, :, cols] = acc_scr[g, :, cols] * fade + (
                        lax.dot_general(v, p.astype(v.dtype), _TN,
                                        preferred_element_type=jnp.float32))

    @pl.when((phase == 1) & (j == last))
    def _():
        for h in range(hq):
            g, c = divmod(h, group)
            cols = pl.ds(c * bq, bq)
            o_ref[h] = (acc_scr[g, :, cols] / l_scr[g, :, cols]).T.astype(
                o_ref.dtype)


def _blocks(t: int) -> tuple[int, int, int]:
    """``(query block, key tile, padded length)`` of a block of ``t``
    positions: one block where ``t`` is at most ``Q_BLOCK``."""
    if t <= Q_BLOCK:
        return t, t, t
    tp = -(-t // Q_BLOCK) * Q_BLOCK
    return Q_BLOCK, K_BLOCK if tp % K_BLOCK == 0 else Q_BLOCK, tp


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "interpret"),
                   inline=True)
def _prefill_call(q, k, v, qi, w, ki, *, top_k: int, scale: float,
                  interpret: bool):
    """One sequence: ``q`` (T, Hq, D), ``k``, ``v`` (T, Hk, D), ``qi`` (T,
    Hi, Di), ``w`` (T, Hi), ``ki`` (T, Di) -> (T, Hq, D).  Under one
    inlined ``jax.jit``: the layers of a program share one trace of the
    kernel."""
    t, hq, d = q.shape
    hk, hi, di = k.shape[1], qi.shape[1], qi.shape[2]
    bq, bk, tp = _blocks(t)
    if tp > t:      # pads lie after every real query: never selected
        q, k, v, qi, w, ki = (
            jnp.pad(x, ((0, tp - t),) + ((0, 0),) * (x.ndim - 1))
            for x in (q, k, v, qi, w, ki))
    nq, nk = tp // bq, tp // bk
    heads_first = lambda x: jnp.swapaxes(x, 0, 1)

    def last_tile(i):
        return (i * bq + bq - 1) // bk

    group = hq // hk
    # a piece of phase 1: as many whole heads of a group as STRIP holds
    strip = bq * math.gcd(group, max(1, STRIP // bq))
    o = pl.pallas_call(
        functools.partial(_prefill_kernel, bq=bq, bk=bk, top_k=top_k,
                          scale=scale, strip=strip),
        name="dsa_prefill",
        grid=(nq, 2, nk),
        in_specs=[
            pl.BlockSpec((bq, hi), lambda i, p, j: (i, 0)),
            pl.BlockSpec((hi, bq, di), lambda i, p, j: (0, i, 0)),
            pl.BlockSpec((di, bk), lambda i, p, j: (0, jnp.where(
                p == 0, jnp.minimum(j, last_tile(i)), last_tile(i)))),
            pl.BlockSpec((hq, bq, d), lambda i, p, j: (0, i, 0)),
            pl.BlockSpec((hk, bk, d), lambda i, p, j: (0, jnp.where(
                p == 1, jnp.minimum(j, last_tile(i)), 0), 0)),
            pl.BlockSpec((hk, bk, d), lambda i, p, j: (0, jnp.where(
                p == 1, jnp.minimum(j, last_tile(i)), 0), 0)),
        ],
        out_specs=pl.BlockSpec((hq, bq, d), lambda i, p, j: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((hq, tp, d), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((nk, bq, bk), jnp.int32),
            pltpu.VMEM((bq, 1), jnp.int32),
            pltpu.VMEM((bq, 1), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hk, d, group * bq), q.dtype),
            pltpu.VMEM((hk, 1, group * bq), jnp.float32),
            pltpu.VMEM((hk, 1, group * bq), jnp.float32),
            pltpu.VMEM((hk, d, group * bq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(w.astype(jnp.float32), heads_first(qi), ki.T, heads_first(q),
      heads_first(k), heads_first(v))
    return heads_first(o)[:t]


def dsa_prefill(q, k, v, qi, w, ki, *, top_k: int, scale: float,
                interpret: bool | None = None):
    """Sparse attention over a block from position 0, batch first: ``q``
    (B, T, Hq, D), ``k``, ``v`` (B, T, Hk, D), ``qi`` (B, T, Hi, Di), ``w``
    (B, T, Hi) float32, ``ki`` (B, T, Di) -> (B, T, Hq, D).  The kernel on
    the TPU (or in the interpreter where ``interpret`` asks for it), the
    plain form elsewhere."""
    interpret = bool(interpret) and resolve_interpret(True)
    if not (interpret or on_tpu()):
        return prefill_plain(q, k, v, qi, w, ki, top_k=top_k, scale=scale)
    call = functools.partial(_prefill_call, top_k=top_k, scale=scale,
                             interpret=interpret)
    if q.shape[0] == 1:
        return call(*(x[0] for x in (q, k, v, qi, w, ki)))[None]
    return jax.vmap(call)(q, k, v, qi, w, ki)


# -------------------------------------------------------------- the step

def _last_tile(pos, bk: int, tiles: int):
    """The index tile that holds ``pos`` (the last a slot reads); a
    position past the table reads to its end."""
    return jnp.minimum(pos // bk, tiles - 1)


def _index_kernel(pos_ref, put_ref, qi_ref, w_ref, new_ref, ki_ref, o_ref,
                  ki_hbm, tile_scr, sem, *, bk: int, max_len: int):
    """One (slot, index tile) grid step; ``ki_hbm`` is the whole table,
    aliased to the input whose blocks ``ki_ref`` are."""
    b, j = pl.program_id(0), pl.program_id(1)
    last = _last_tile(pos_ref[b], bk, pl.num_programs(1))
    put = put_ref[b]
    # the slot writes its new key where its write position lies in a tile
    # it reads (a position past the table is no tile's)
    writes = (put >= 0) & (put < max_len) & (put // bk <= last)
    here = writes & (j == put // bk)

    def write():
        return pltpu.make_async_copy(
            tile_scr, ki_hbm.at[b, :, pl.ds((put // bk) * bk, bk)], sem.at[0])

    def score(tile):
        r = jnp.dot(qi_ref[0], tile, preferred_element_type=jnp.float32)
        o_ref[0] = jnp.sum(w_ref[0] * jnp.maximum(r, 0.0), axis=0,
                           keepdims=True)

    @pl.when((j <= last) & ~here)
    def _():
        score(ki_ref[0])

    @pl.when(here)
    def _():
        lane = j * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        tile_scr[...] = jnp.where(
            lane == put, new_ref[0].astype(jnp.float32),
            ki_ref[0].astype(jnp.float32)).astype(tile_scr.dtype)
        write().start()
        score(tile_scr[...])

    # the write overlaps the slot's later tiles: wait for it at the slot's
    # last step, before the next slot's tile takes the scratch
    @pl.when(writes & (j == pl.num_programs(1) - 1))
    def _():
        write().wait()


@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def _index_call(qi, w, index_keys, new_key, pos, put, *, interpret: bool):
    slots, hi, di = qi.shape
    max_len = index_keys.shape[2]
    bk = min(STEP_BLOCK, max_len)
    tiles = -(-max_len // bk)
    if tiles * bk != max_len:   # whole tiles: the rows past the table
        index_keys = jnp.pad(   # lie past every position, never selected
            index_keys, ((0, 0), (0, 0), (0, tiles * bk - max_len)))
    held = lambda b, j, p, u: (
        b, 0, jnp.minimum(j, _last_tile(p[b], bk, tiles)))
    one = lambda b, j, p, u: (b, 0, 0)
    scores, index_keys = pl.pallas_call(
        functools.partial(_index_kernel, bk=bk, max_len=max_len),
        name="dsa_step_index",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots, tiles),
            in_specs=[pl.BlockSpec((1, hi, di), one),
                      pl.BlockSpec((1, hi, 1), one),
                      pl.BlockSpec((1, di, 1), one),
                      pl.BlockSpec((1, di, bk), held)],
            out_specs=[pl.BlockSpec((1, 1, bk), held),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((di, bk), index_keys.dtype),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=[jax.ShapeDtypeStruct((slots, 1, tiles * bk), jnp.float32),
                   jax.ShapeDtypeStruct(index_keys.shape, index_keys.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos.astype(jnp.int32), put.astype(jnp.int32), qi,
      w.astype(jnp.float32)[..., None],
      new_key.astype(index_keys.dtype)[..., None], index_keys)
    return scores[:, 0, :max_len], index_keys[:, :, :max_len]


def dsa_step_index(qi, w, index_keys, new_key, pos, put=None, *,
                   interpret: bool | None = None):
    """The step's indexer scores, with the round's new index key written
    first: ``qi`` (slots, Hi, Di), ``w`` (slots, Hi), ``index_keys``
    (slots, Di, max_len), ``new_key`` (slots, Di) written at ``put``
    (slots,; ``pos`` where None; a position past the table writes nothing),
    ``pos`` (slots,) the position each slot's query is at -> the scores
    (slots, max_len) float32, computed (and read) only to each slot's
    position (what lies past it is not a score), and the index rows."""
    put = pos if put is None else put
    interpret = bool(interpret) and resolve_interpret(True)
    if not (interpret or on_tpu()):
        return step_index_plain(qi, w, index_keys, new_key, pos, put)
    return _index_call(qi, w, index_keys, new_key, pos, put,
                       interpret=interpret)


def _attend_kernel(count_ref, q_ref, k_ref, v_ref, o_ref, *, scale: float,
                   group: int):
    b = pl.program_id(0)
    hq, d = q_ref.shape[1:]
    rows = k_ref.shape[1]
    real = lax.broadcasted_iota(jnp.int32, (group, rows), 1) < count_ref[b]
    q = q_ref[0].astype(jnp.float32)
    out = []
    for g in range(hq // group):
        qg = q[g * group:(g + 1) * group].astype(k_ref.dtype)
        kg = k_ref[0, :, g * d:(g + 1) * d]
        s = lax.dot_general(qg, kg, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(real, s, NEG)
        p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
        o = jnp.dot(p.astype(v_ref.dtype), v_ref[0, :, g * d:(g + 1) * d],
                    preferred_element_type=jnp.float32)
        out.append(o / jnp.sum(p, axis=1, keepdims=True))
    o_ref[0] = jnp.concatenate(out, axis=0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"),
                   inline=True)
def _attend_call(q, keys, values, count, *, scale: float, interpret: bool):
    slots, hq, d = q.shape
    rows, width = keys.shape[1:]
    one = lambda b, c: (b, 0, 0)
    return pl.pallas_call(
        functools.partial(_attend_kernel, scale=scale,
                          group=hq // (width // d)),
        name="dsa_step_attend",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots,),
            in_specs=[pl.BlockSpec((1, hq, d), one),
                      pl.BlockSpec((1, rows, width), one),
                      pl.BlockSpec((1, rows, width), one)],
            out_specs=pl.BlockSpec((1, hq, d), one)),
        out_shape=jax.ShapeDtypeStruct((slots, hq, d), values.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(count.astype(jnp.int32), q, keys, values)


def dsa_step_attend(q, keys, values, count, *, scale: float,
                    interpret: bool | None = None):
    """The step's attention over each slot's gathered rows: ``q`` (slots,
    Hq, D), ``keys``, ``values`` (slots, K, Hk * D), ``count`` (slots,) ->
    (slots, Hq, D)."""
    interpret = bool(interpret) and resolve_interpret(True)
    if not (interpret or on_tpu()):
        return step_attend_plain(q, keys, values, count, scale=scale)
    return _attend_call(q, keys, values, count, scale=scale,
                        interpret=interpret)
