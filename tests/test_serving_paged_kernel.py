"""Pallas paged decode-attention kernel (ISSUE 16): the fused
``ops/paged_attention.py`` kernel pinned against its pure-jnp oracle
``paged_attention_reference`` — MHA and GQA head layouts, the decode
(l_q=1) and speculative-verify (l_q=k+1) query widths, in-kernel int8
dequant, block-table aliasing, the per-slot length mask, and the GSPMD
mesh variant.  Everything runs in Pallas interpret mode on this
container's CPU devices (the kernel's off-TPU default).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.ops.paged_attention import (
    paged_attention, paged_attention_reference)


def _case(seed, *, s=4, l_q=1, h=4, kvh=None, d=8, blk=4, mb=4,
          n=None, int8=False):
    """Random pools + a PERMUTED block table (physical ids deliberately
    non-contiguous and out of order — the indirection under test) and
    in-range positions leaving every query row at least one valid key."""
    kvh = kvh if kvh is not None else h
    n = n if n is not None else s * mb + 2
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((s, l_q, h, d)), jnp.float32)
    if int8:
        k_pool = jnp.asarray(
            rng.integers(-127, 128, (n, kvh, blk, d)), jnp.int8)
        v_pool = jnp.asarray(
            rng.integers(-127, 128, (n, kvh, blk, d)), jnp.int8)
        k_scale = jnp.asarray(
            rng.uniform(0.5, 1.5, (n, kvh, blk)) / 127.0, jnp.float32)
        v_scale = jnp.asarray(
            rng.uniform(0.5, 1.5, (n, kvh, blk)) / 127.0, jnp.float32)
    else:
        k_pool = jnp.asarray(
            rng.standard_normal((n, kvh, blk, d)), jnp.float32)
        v_pool = jnp.asarray(
            rng.standard_normal((n, kvh, blk, d)), jnp.float32)
        k_scale = v_scale = None
    bt = jnp.asarray(
        rng.permutation(n)[:s * mb].reshape(s, mb), jnp.int32)
    pos = jnp.asarray(
        rng.integers(1, mb * blk - l_q + 1, s), jnp.int32)
    return q, k_pool, v_pool, bt, pos, k_scale, v_scale


def _both(q, k_pool, v_pool, bt, pos, k_scale=None, v_scale=None):
    out = paged_attention(q, k_pool, v_pool, bt, pos,
                          k_scale=k_scale, v_scale=v_scale)
    ref = paged_attention_reference(q, k_pool, v_pool, bt, pos,
                                    k_scale=k_scale, v_scale=v_scale)
    return np.asarray(out), np.asarray(ref)


def test_kernel_matches_reference_decode_mha():
    """l_q=1 MHA decode: the fused online-softmax accumulation matches
    the dense masked-softmax oracle to f32 reassociation tolerance."""
    out, ref = _both(*_case(0)[:5])
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=2e-5)


def test_kernel_matches_reference_gqa():
    """GQA (heads=4 over kv_heads=2): the kernel folds query groups into
    the kv-head grid axis; the oracle widens kv heads by repeat — same
    numbers either way."""
    q, k, v, bt, pos, _, _ = _case(1, h=4, kvh=2)
    out, ref = _both(q, k, v, bt, pos)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=2e-5)


def test_kernel_block_query_verify_width():
    """The (slots, k+1) speculative-verify variant: each query row r
    attends keys ``t <= pos + r`` — the staircase mask the verify
    program's correctness rests on."""
    q, k, v, bt, pos, _, _ = _case(2, l_q=3, h=4, kvh=2)
    out, ref = _both(q, k, v, bt, pos)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=2e-5)
    # the staircase is real: row 0 recomputed standalone (l_q=1 at the
    # same position) equals row 0 of the block-query call
    solo = np.asarray(paged_attention(q[:, :1], k, v, bt, pos))
    np.testing.assert_allclose(solo[:, 0], out[:, 0],
                               rtol=1e-5, atol=2e-5)


def test_kernel_int8_dequant_matches_reference():
    """int8 pools + per-vector f32 scales: the kernel dequantizes inside
    the block loop; the oracle dequantizes the whole gather — identical
    math, no materialized f32 pool in the fused path."""
    q, k, v, bt, pos, ks, vs = _case(3, h=4, kvh=2, int8=True)
    out, ref = _both(q, k, v, bt, pos, ks, vs)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=2e-5)


def test_kernel_reads_through_block_aliases():
    """Zero-copy semantics at the kernel level: two slots whose TABLES
    point at the same physical blocks compute identical outputs for
    identical queries — sharing is invisible to the read path."""
    q, k, v, bt, pos, _, _ = _case(4, s=2)
    bt = jnp.stack([bt[0], bt[0]])            # slot 1 aliases slot 0
    pos = jnp.stack([pos[0], pos[0]])
    q = jnp.stack([q[0], q[0]])
    out = np.asarray(paged_attention(q, k, v, bt, pos))
    np.testing.assert_array_equal(out[0], out[1])
    ref = np.asarray(paged_attention_reference(q, k, v, bt, pos))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=2e-5)


def test_kernel_masks_tail_and_unmapped_blocks():
    """The length mask is the ONLY thing protecting reads past a slot's
    position: corrupting pool contents beyond ``pos`` — including whole
    blocks the table maps but the slot never reached — must not change
    the output (the 'unmapped entries hold a valid index' contract)."""
    q, k, v, bt, pos, _, _ = _case(5, s=3, mb=4, blk=4)
    pos = jnp.asarray([2, 5, 9], jnp.int32)   # slots end inside block 0/1/2
    base = np.asarray(paged_attention(q, k, v, bt, pos))
    # poison every pool position strictly past each slot's own pos —
    # conservatively: rebuild pools with garbage in any block only
    # reachable as a DEAD region (per-slot tail blocks)
    k2, v2 = np.array(k), np.array(v)
    for s_i, p_i in enumerate([2, 5, 9]):
        first_dead = p_i // 4 + 1
        for j in range(first_dead, 4):
            bid = int(np.asarray(bt)[s_i, j])
            k2[bid] = 1e4
            v2[bid] = -1e4
    out = np.asarray(paged_attention(q, jnp.asarray(k2), jnp.asarray(v2),
                                     bt, pos))
    np.testing.assert_array_equal(base, out)


@pytest.mark.parametrize("pass_mesh", [False, True])
def test_kernel_under_gspmd_mesh(mesh8, pass_mesh):
    """The serving layout under jit: queries/tables/positions sharded
    over slots on the 8-way data axis, pools replicated (any slot reads
    any block) — the partitioned program still matches the oracle.  With
    the mesh passed, the call runs under shard_map over 'data' (what the
    paged cache does: Mosaic kernels cannot be GSPMD-partitioned on the
    chip); without it, the CPU interpreter rides GSPMD."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu.parallel import mesh as meshlib

    q, k, v, bt, pos, _, _ = _case(6, s=8)
    repl = NamedSharding(mesh8, P())
    row = NamedSharding(mesh8, P(meshlib.DATA_AXIS))
    qd = jax.device_put(q, NamedSharding(
        mesh8, P(meshlib.DATA_AXIS, None, None, None)))
    btd = jax.device_put(bt, NamedSharding(mesh8, P(meshlib.DATA_AXIS,
                                                    None)))
    posd = jax.device_put(pos, row)
    kd, vd = jax.device_put(k, repl), jax.device_put(v, repl)
    fn = functools.partial(paged_attention,
                           mesh=mesh8 if pass_mesh else None)
    out = np.asarray(jax.jit(fn)(qd, kd, vd, btd, posd))
    ref = np.asarray(paged_attention_reference(q, k, v, bt, pos))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=2e-5)


def test_kernel_rejects_bad_head_and_scale_combos():
    q, k, v, bt, pos, ks, vs = _case(7, h=4, kvh=2, int8=True)
    with pytest.raises(ValueError, match="together"):
        paged_attention(q, k, v, bt, pos, k_scale=ks)
    with pytest.raises(ValueError, match="divisible"):
        paged_attention(q[:, :, :3], k, v, bt, pos,
                        k_scale=ks, v_scale=vs)
