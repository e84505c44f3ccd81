"""Flash-attention Pallas kernel vs the dense oracle.

The oracle is `parallel.ring_attention.dense_attention` (itself validated
against plain softmax math in test_ring_attention.py).  Kernels run in
Pallas interpret mode on the CPU fake mesh — same code path the TPU
compiles (SURVEY.md §4: unit tests on the fake mesh are the analogue of the
reference's fork-based fake cluster).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.ops import flash_attention
from distributed_tensorflow_tpu.parallel.ring_attention import dense_attention


def _qkv(key, b, l, h, d, lk=None):
    kq, kk, kv = jax.random.split(key, 3)
    lk = lk or l
    q = jax.random.normal(kq, (b, l, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, lk, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, lk, h, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_matches_dense_single_block(causal):
    q, k, v = _qkv(jax.random.key(0), 2, 16, 2, 8)
    out = flash_attention(q, k, v, causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_matches_dense_multi_block(causal):
    # L=64 with 16-wide blocks → 4×4 grid exercises the online-softmax merge
    q, k, v = _qkv(jax.random.key(1), 2, 64, 2, 8)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_padding_non_divisible_lengths():
    # L=50 not divisible by 16 → kernel pads internally and slices back
    q, k, v = _qkv(jax.random.key(2), 1, 50, 2, 8)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_kv_mask():
    q, k, v = _qkv(jax.random.key(3), 2, 32, 2, 8)
    mask = (jax.random.uniform(jax.random.key(4), (2, 32)) > 0.3)
    mask = mask.at[:, 0].set(True)  # keep ≥1 valid key per row
    out = flash_attention(q, k, v, kv_mask=mask.astype(jnp.float32),
                          block_q=16, block_k=16)
    ref = dense_attention(q, k, v, kv_mask=mask.astype(jnp.float32))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_cross_attention_lengths():
    q, k, v = _qkv(jax.random.key(5), 1, 32, 2, 8, lk=48)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_dense(causal):
    q, k, v = _qkv(jax.random.key(6), 2, 32, 2, 8)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        return jnp.sum(jnp.sin(o))  # non-trivial upstream gradient

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(dense_attention(q, k, v, causal=causal)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.slow
def test_gradients_with_mask_and_padding():
    q, k, v = _qkv(jax.random.key(7), 1, 40, 2, 8)
    mask = jnp.ones((1, 40)).at[:, 33:].set(0.0)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, kv_mask=mask, block_q=16, block_k=16)
        return jnp.sum(o * o)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, kv_mask=mask) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_jit_and_vmap_compose():
    q, k, v = _qkv(jax.random.key(8), 2, 32, 2, 8)
    jitted = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, block_q=16, block_k=16, interpret=True))
    np.testing.assert_allclose(jitted(q, k, v), dense_attention(q, k, v),
                               atol=1e-5, rtol=1e-5)


def test_bfloat16_inputs():
    q, k, v = _qkv(jax.random.key(9), 1, 32, 2, 8)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, block_q=16, block_k=16)
    ref = dense_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(jnp.float32), ref,
                               atol=3e-2, rtol=3e-2)


def test_block_sizes_validated_against_vmem():
    """Oversized blocks fail fast with a clear ValueError instead of an
    opaque Mosaic allocation error (VERDICT r2 weak #8)."""
    import jax.numpy as jnp
    import pytest

    from distributed_tensorflow_tpu.ops import flash_attention

    q = jnp.ones((1, 1 << 16, 1, 256), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        # interpret=False: exercise the kernel path's validation (the
        # check fires before any pallas_call is built)
        flash_attention(q, q, q, block_q=1 << 16, block_k=1 << 16,
                        interpret=False)


# ---------------------------------------------------------------------------
# PR 34: operands in the dtype they arrive in, tiles of three kinds, blocks
# chosen from the shapes
# ---------------------------------------------------------------------------

def _flash_module():
    # ops/__init__ re-exports the flash_attention FUNCTION under the
    # module's name, so the module itself comes through importlib
    import importlib
    return importlib.import_module(
        "distributed_tensorflow_tpu.ops.flash_attention")


def _out_and_grads(attend, q, k, v):
    """The output and d(sum(sin(out)))/d(q, k, v), all float32."""
    def loss(q, k, v):
        out = attend(q, k, v)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return [x.astype(jnp.float32) for x in (out, *grads)]


def _case_inputs(masked):
    # L 256 at blocks of 64: 4 x 4 tiles a head, so that under `causal`
    # skipped, diagonal and wholly-past tiles all occur
    q, k, v = _qkv(jax.random.key(34), 2, 256, 2, 16)
    mask = None
    if masked:
        mask = (jax.random.uniform(jax.random.key(35), (2, 256)) > 0.3)
        mask = mask.at[:, 0].set(True).astype(jnp.float32)
    return q, k, v, mask


# what bfloat16 operands cost against the float32 oracle on the float32
# copies of the same inputs: the inputs' own rounding is not in it, p and
# ds rounded to 8 bits of mantissa before their products is (2^-9 relative
# an element, averaged over a row's keys)
BF16_ATOL = 2e-2


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype,atol", [(jnp.bfloat16, BF16_ATOL),
                                        (jnp.float32, 1e-5)])
def test_output_and_gradients_match_dense_in_both_dtypes(dtype, atol, causal,
                                                         masked):
    q, k, v, mask = _case_inputs(masked)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=causal, kv_mask=mask,
                                        block_q=64, block_k=64), q, k, v)
    assert got[0].dtype == jnp.float32
    # the oracle on the float32 copies of the SAME (already rounded) inputs
    want = _out_and_grads(
        lambda q, k, v: dense_attention(q, k, v, causal=causal, kv_mask=mask),
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, atol=atol, rtol=atol,
                                   err_msg=f"{name} mismatch")


def test_results_keep_the_inputs_dtype():
    q, k, v, _ = _case_inputs(False)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    grads = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, block_q=64, block_k=64).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3


@pytest.mark.parametrize("masked", [False, True])
def test_results_do_not_depend_on_the_tiling(masked):
    """64 x 64 blocks skip 6 of 16 tiles under `causal`; a key block as
    long as the sequence can skip none.  Both give the same numbers to
    float32 round-off."""
    q, k, v, mask = _case_inputs(masked)
    fa = _flash_module()
    assert fa.causal_tiles(256, 256, 64, 64, True) == (10, 16)
    assert fa.causal_tiles(256, 256, 64, 256, True) == (16, 16)
    tiled, whole = (
        _out_and_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, kv_mask=mask, block_q=64, block_k=bk),
            q, k, v)
        for bk in (64, 256))
    for a, b in zip(tiled, whole):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("dtype,atol", [(jnp.bfloat16, BF16_ATOL),
                                        (jnp.float32, 1e-5)])
def test_the_staircase_of_a_square_diagonal_tile(dtype, atol, masked,
                                                 monkeypatch):
    """A square block that holds several strips is walked as a staircase:
    each strip of queries against the keys up to its own end.  With the
    strips shrunk to 64, 32 and 16 queries, 128 x 128 blocks at L 256 give
    every kernel diagonal tiles of several strips beside a plain past tile
    and a skipped future one."""
    fa = _flash_module()
    monkeypatch.setattr(fa, "_STRIP", {"fwd": 64, "dq": 32, "dkv": 16})
    assert fa.causal_tiles(256, 256, 128, 128, True, "dkv") == (136, 256)
    q, k, v, mask = _case_inputs(masked)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=True, kv_mask=mask,
                                        block_q=128, block_k=128), q, k, v)
    want = _out_and_grads(
        lambda q, k, v: dense_attention(q, k, v, causal=True, kv_mask=mask),
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, atol=atol, rtol=atol,
                                   err_msg=f"{name} mismatch")


def test_the_chosen_blocks_at_the_trained_length():
    """L 1,024 with the blocks and strips the chip chose: one grid step a
    head, every kernel's staircase several strips deep."""
    q, k, v = _qkv(jax.random.key(37), 1, 1024, 1, 8)
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
    want = _out_and_grads(
        lambda q, k, v: dense_attention(q, k, v, causal=True), q, k, v)
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5,
                                   err_msg=f"{name} mismatch")


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_block_choice_lets_the_causal_skip_engage(kernel):
    """The mechanism's counter: at the training cells' shape every kernel
    computes at most 3/4 of the square under `causal`, all of it without.
    What is skipped is decided by the strip a piece takes, so the count is
    made over strip x strip squares, with `_causal_skip` in plain Python."""
    fa = _flash_module()
    bq, bk = fa._choose_blocks(1024, 1024, 64, 2, True)
    assert bk <= bq
    sub = fa._strip_width(kernel, bq)
    squares = [(a, b) for a in range(1024 // sub) for b in range(1024 // sub)]
    run = [t for t in squares if not fa._causal_skip(*t, sub, sub)]
    assert len(run) <= 0.75 * len(squares)
    assert fa.causal_tiles(1024, 1024, bq, bk, True, kernel) == (
        len(run), len(squares))
    fa._check_vmem_budget(bq, bk, 64, 2)

    bq, bk = fa._choose_blocks(1024, 1024, 64, 2, False)
    run, total = fa.causal_tiles(1024, 1024, bq, bk, False, kernel)
    assert run == total
    fa._check_vmem_budget(bq, bk, 64, 2)


@pytest.mark.parametrize("lq,lk,d,itemsize,causal", [
    (1024, 1024, 64, 2, True), (1024, 1024, 64, 4, True),
    (8192, 8192, 128, 4, True), (4096, 4096, 256, 4, False),
    (1536, 1536, 64, 2, True), (1100, 1100, 64, 2, True),
    (32768, 32768, 512, 4, True), (50, 50, 8, 4, True),
    (32, 48, 8, 4, False), (128, 65536, 512, 4, False)])
def test_chosen_blocks_fit_vmem_and_the_lengths(lq, lk, d, itemsize, causal):
    fa = _flash_module()
    bq, bk = fa._choose_blocks(lq, lk, d, itemsize, causal)
    assert 0 < bq <= lq and 0 < bk <= lk
    if causal:
        assert bk <= bq
    fa._check_vmem_budget(bq, bk, d, itemsize)


def test_budget_counts_operands_at_their_own_width():
    fa = _flash_module()
    assert (fa._vmem_need(512, 512, 64, 4) - fa._vmem_need(512, 512, 64, 2)
            == 2 * (2 * 512 + 2 * 512) * 64 * 2)
    with pytest.raises(ValueError, match="VMEM"):
        fa._check_vmem_budget(2048, 2048, 64, 4)      # refused on the chip


def test_explicit_blocks_override_the_choice_each_on_its_own():
    fa = _flash_module()
    chosen = fa._choose_blocks(1024, 1024, 64, 2, True)
    assert fa._resolve_blocks(None, None, 1024, 1024, 64, 2, True) == chosen
    assert fa._resolve_blocks(256, None, 1024, 1024, 64, 2, True) == (
        256, chosen[1])
    assert fa._resolve_blocks(None, 1024, 1024, 1024, 64, 2, True) == (
        chosen[0], 1024)
    assert fa._resolve_blocks(512, 1024, 1024, 1024, 64, 2, True) == (
        512, 1024)
    assert fa._resolve_blocks(512, 1024, 50, 70, 8, 4, False) == (50, 70)


@pytest.mark.parametrize("causal", [False, True])
def test_chosen_blocks_pad_lengths_they_do_not_divide(causal, monkeypatch):
    """Lengths that the blocks do not divide still pad as before: with the
    choice shrunk to 16-wide tiles, L 50 pads to 64 and slices back."""
    fa = _flash_module()
    monkeypatch.setattr(fa, "_choose_blocks", lambda *a: (16, 16))
    q, k, v = _qkv(jax.random.key(36), 1, 50, 2, 8)
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=causal), q, k, v)
    want = _out_and_grads(
        lambda q, k, v: dense_attention(q, k, v, causal=causal), q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
