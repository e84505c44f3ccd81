"""Selective scan (the Mamba-1 recurrence) as one Pallas TPU kernel.

    S_t[d, n] = exp(dt_t[d] A[d, n]) S_{t-1}[d, n] + dt_t[d] u_t[d] B_t[n]
    y_t[d]    = sum_n S_t[d, n] C_t[n] + D[d] u_t[d]

The decay differs for every channel ``d`` AND every state index ``n``, so
there is no matrix-product form of it (``models/hybrid_ssm.ssd_chunked``
needs ONE scalar decay a head): the work is ``L`` dependent elementwise
updates of a ``(D, N)`` state.  A ``lax.scan`` of that update moves the
state through HBM once a position; this kernel holds it on chip.

What one grid step does.  The grid is ``(batch, channel blocks, sequence
blocks)``; the sequence axis is the last and runs sequentially, so the
float32 state of one channel block stays in VMEM scratch from one sequence
block to the next (it is initialised at the first and written out at the
last).  The channel axis is folded into whole ``(8, 128)`` register tiles:
``u``, ``dt`` and ``y`` enter and leave as ``(L, D / 128, 128)``, a channel
block is 8 rows of 128 lanes (1,024 channels, one vreg a position), and the
state of a channel block is ``N`` such tiles, ``(N, 8, 128)``.  ``B_t`` and
``C_t`` are the same for every channel, so they are SCALARS to the update
and live in SMEM: a position costs, for each ``n``, one exponential and six
multiply-adds on one vreg, with no broadcast across lanes or sublanes and
no reduction (the sum over ``n`` is a sum of ``N`` tiles).  Inside a grid
step a ``fori_loop`` walks the block's positions with the ``N`` state tiles
as its carry.

A position whose ``dt`` is 0 leaves the state as it is (``exp(0) = 1``, and
it feeds ``0``): that is how a prefill bucket's pads are made inert, as in
``ssd_chunked``.  Forward only (no ``custom_vjp``): training through this
kernel is not built.

Off the TPU the same kernel runs in the Pallas interpreter
(``ops/flash_attention.resolve_interpret`` is the repo's rule);
``selective_scan_plain`` is the ``lax.scan`` of the one-token update that
the tests hold it to."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_tpu.ops.flash_attention import resolve_interpret

LANES = 128
TILE_ROWS = 8           # a channel block: 8 x 128 channels, one vreg
# positions a grid step, and how many of them one iteration of the loop
# takes (Mosaic unrolls a loop fully or not at all, so the body is written
# out).  Measured on the v5e at 32,768 positions x 5,120 channels (PERF.md
# section 6): the block hardly matters (128 to 512), and 8 positions an
# iteration are 10% faster than 1 (13.5 against 15.1 ms a call with its
# re-layouts) but every call site is compiled by Mosaic on its own, 26 of
# them a prefill program, and at 8 the five programs of the 32k cell took
# 19 minutes to trace and build where at 2 they take four
SEQ_BLOCK = 256
UNROLL = 2

def selective_step(state, u, dt, a, b, c, d_skip):
    """One token.  ``state`` (B, D, N) float32, ``u``, ``dt`` (B, D), ``a``
    (D, N), ``b``, ``c`` (B, N), ``d_skip`` (D,) -> ``y`` (B, D) float32 and
    the new state."""
    u, dt = u.astype(jnp.float32), dt.astype(jnp.float32)
    state = jnp.exp(dt[..., None] * a) * state \
        + (dt * u)[..., None] * b.astype(jnp.float32)[:, None, :]
    y = jnp.sum(state * c.astype(jnp.float32)[:, None, :], -1)
    return y + d_skip.astype(jnp.float32) * u, state


def selective_scan_plain(u, dt, a, b, c, d_skip, initial_state=None):
    """``selective_scan`` as a ``lax.scan`` of ``selective_step``: what the
    kernel is held to."""
    bsz, _, d = u.shape
    if initial_state is None:
        initial_state = jnp.zeros((bsz, d, a.shape[1]), jnp.float32)

    def step(s, inp):
        u_t, dt_t, b_t, c_t = inp
        y, s = selective_step(s, u_t, dt_t, a.astype(jnp.float32), b_t, c_t,
                              d_skip)
        return s, y

    last, y = lax.scan(step, initial_state.astype(jnp.float32),
                       tuple(jnp.moveaxis(t, 1, 0) for t in (u, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), last


def _kernel(b_ref, c_ref, u_ref, dt_ref, a_ref, d_ref, s0_ref,
            y_ref, last_ref, state, *, n: int, block: int, unroll: int):
    seq = pl.program_id(2)

    @pl.when(seq == 0)
    def _():
        state[...] = s0_ref[0]

    d_skip = d_ref[...]

    def position(t, s):
        dt = dt_ref[0, t]
        u = u_ref[0, t]
        fed = dt * u
        y = d_skip * u
        new = []
        for k in range(n):
            sk = jnp.exp(dt * a_ref[k]) * s[k] + fed * b_ref[t * n + k]
            y = y + sk * c_ref[t * n + k]
            new.append(sk)
        y_ref[0, t] = y
        return tuple(new)

    def body(i, s):     # Mosaic unrolls a loop fully or not at all
        for r in range(unroll):
            s = position(i * unroll + r, s)
        return s

    s = lax.fori_loop(0, block // unroll, body,
                      tuple(state[k] for k in range(n)))
    for k in range(n):
        state[k] = s[k]

    @pl.when(seq == pl.num_programs(2) - 1)
    def _():
        last_ref[0] = state[...]


def selective_scan(u, dt, a, b, c, d_skip, initial_state=None, *,
                   seq_block: int | None = None,
                   interpret: bool | None = None):
    """The recurrence over a block of ``L`` positions, batch first.

    ``u`` (B, L, D) the mixer's activated input; ``dt`` (B, L, D) after the
    softplus, 0 where the position is a pad; ``a`` (D, N) negative; ``b``,
    ``c`` (B, L, N); ``d_skip`` (D,); ``initial_state`` (B, D, N) or None
    for zeros.  Returns ``y`` (B, L, D) float32 and the state after the
    last position (B, D, N) float32.  Everything is computed in float32.

    The call is one inlined ``jax.jit``: a model's state-space layers share
    one trace and one lowering of the kernel a shape (26 traces of the
    written-out loop body were most of a prefill program's tracing), and
    the custom call keeps the ``pallas_call``'s name."""
    return _scan(u, dt, a, b, c, d_skip, initial_state,
                 block=min(seq_block or SEQ_BLOCK, u.shape[1]), unroll=UNROLL,
                 interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block", "unroll", "interpret"),
                   inline=True)
def _scan(u, dt, a, b, c, d_skip, initial_state, *, block: int, unroll: int,
          interpret: bool):
    bsz, length, d = u.shape
    n = a.shape[1]
    f32 = jnp.float32
    pad_l, pad_d = -length % block, -d % LANES
    groups = (d + pad_d) // LANES
    rows = TILE_ROWS if groups % TILE_ROWS == 0 else groups

    def fold(t):
        """(..., D) -> (..., D / 128, 128), channels padded to whole
        lanes."""
        t = t.astype(f32)
        if pad_d:
            t = jnp.pad(t, ((0, 0),) * (t.ndim - 1) + ((0, pad_d),))
        return t.reshape(t.shape[:-1] + (groups, LANES))

    def over_l(t):      # pads of the sequence: dt = 0 there
        return jnp.pad(t, ((0, 0), (0, pad_l)) + ((0, 0),) * (t.ndim - 2)) \
            if pad_l else t

    uf, dtf = fold(over_l(u)), fold(over_l(dt))
    af = fold(a.astype(f32).T)                              # (N, G, 128)
    s0 = jnp.zeros((bsz, n, groups, LANES), f32) if initial_state is None \
        else fold(jnp.swapaxes(initial_state, 1, 2))        # (B, N, G, 128)
    bf, cf = (over_l(t).astype(f32).reshape(-1) for t in (b, c))
    n_seq = (length + pad_l) // block

    seq_spec = pl.BlockSpec((1, block, rows, LANES),
                            lambda i, j, l: (i, l, j, 0))
    state_spec = pl.BlockSpec((1, n, rows, LANES),
                              lambda i, j, l: (i, 0, j, 0))
    scalar_spec = pl.BlockSpec((block * n,), lambda i, j, l: (i * n_seq + l,),
                               memory_space=pltpu.SMEM)
    y, last = pl.pallas_call(
        functools.partial(_kernel, n=n, block=block,
                          unroll=unroll if block % unroll == 0 else 1),
        name="selective_scan",
        grid=(bsz, groups // rows, n_seq),
        in_specs=[scalar_spec, scalar_spec, seq_spec, seq_spec,
                  pl.BlockSpec((n, rows, LANES), lambda i, j, l: (0, j, 0)),
                  pl.BlockSpec((rows, LANES), lambda i, j, l: (j, 0)),
                  state_spec],
        out_specs=[seq_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct(uf.shape, f32),
                   jax.ShapeDtypeStruct(s0.shape, f32)],
        scratch_shapes=[pltpu.VMEM((n, rows, LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(bf, cf, uf, dtf, af, fold(d_skip), s0)
    y = y.reshape(bsz, length + pad_l, groups * LANES)[:, :length, :d]
    last = jnp.swapaxes(last.reshape(bsz, n, groups * LANES)[..., :d], 1, 2)
    return y, last
