"""Gradient-compression codecs: reduced-precision cross-device sync.

The paper's whole subject is the cost of exchanging gradients between
workers; in this TPU-native port that exchange is the per-step
`pmean`/`reduce_scatter` payload the tracer's ``collective_profile``
measures.  This module makes that payload a knob — three codecs behind one
interface, selected by ``--grad-compression {none,bf16,int8}``:

* ``none``  — bitwise-identical passthrough: every collective delegates
  verbatim to :mod:`parallel.collectives`, so the compiled program is the
  same HLO as before the codec existed.
* ``bf16``  — cast to bfloat16 for the exchange: the wire carries
  2 bytes/param instead of 4, and the ring reduction itself runs in bf16
  (the result is widened back to f32 for the optimizer only AFTER the
  collective — nothing widens the in-flight accumulation, the standard
  trade of the production bf16-gradient-allreduce trick).
* ``int8``  — per-leaf max-abs scale + stochastic rounding to int8
  (1 byte/param + one f32 scale per leaf on the wire); f32 master params
  are untouched — only the exchanged value is quantized.  The reduction
  is the standard two-phase compressed allreduce (see
  :class:`Int8Codec`), so per-device traffic is genuinely ~¼ of the
  uncompressed ring allreduce at any device count.  Stochastic rounding
  makes the quantizer unbiased in expectation (the 1-bit-SGD /
  error-feedback lineage's prerequisite), verified in
  tests/test_compression.py.

Two application modes, matching how each engine owns its collective:

* **Explicit collectives** (the shard_map engines — sync DP's gradient
  psum, async local-SGD's periodic parameter ``pmean``, gossip's
  ``neighbor_mean``): the codec wraps the collective itself —
  ``all_reduce_sum``/``all_reduce_mean``/``neighbor_mean`` below encode on
  the sending device, move the compressed representation through the XLA
  collective (bf16 psum / int8 all_to_all+all_gather / int8 ppermute),
  and decode on the receiving side.  The compressed dtype is what
  crosses ICI.
* **Compiler-inserted collectives** (the GSPMD engines — fsdp's
  reduce-scatter, tensor-parallel/composite/expert's data-axis
  all-reduce): XLA owns the collective, so the codec applies
  ``roundtrip`` — quantize→dequantize on the gradient straight after AD —
  which reproduces the *numerics* of a compressed exchange (identical
  quantization error on every replica) while the collective itself still
  moves the original dtype.  ``Engine.grad_collective_bytes`` reports the
  codec's payload accounting in both modes; on these engines it is the
  accounting figure, not the executed transfer (the engine docstrings and
  README say which mode applies where).

All collective wrappers must be called inside a shard_map-mapped function
over the named axis, like their :mod:`parallel.collectives` counterparts
(``jax.vmap`` with an ``axis_name`` emulates them for tests).
"""

from __future__ import annotations

from typing import Any, Iterable

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.parallel import collectives as coll

PyTree = Any

CODECS = ("none", "bf16", "int8")


def _numel(shape) -> int:
    """Element count of a shape tuple — the one place the wire-bytes
    accounting multiplies dimensions."""
    size = 1
    for d in shape:
        size *= int(d)
    return size

# fold tag separating the codec's rounding stream from every other
# consumer of an engine's step rng ("comp" in ASCII) — engines derive
# their key via codec_rng() so the derivation lives in ONE place
_RNG_TAG = 0x636F6D70


def codec_rng(rng: jax.Array) -> jax.Array:
    """The codec's rounding key for a step, derived from the engine's step
    rng.  Engines pass a per-DEVICE rng when each device quantizes its own
    local value (sync grads, async/gossip params — independence is what
    averages the rounding noise out), and an axis-INVARIANT rng when the
    quantized value is replicated (the GSPMD roundtrip — a per-device key
    would silently diverge the replicas)."""
    return jax.random.fold_in(rng, _RNG_TAG)


def _leaf_rngs(tree: PyTree, rng):
    """One independent key per leaf (same traversal order as tree.map), or
    all-None when no rng was provided (deterministic rounding)."""
    leaves, treedef = jax.tree.flatten(tree)
    if rng is None:
        return jax.tree.unflatten(treedef, [None] * len(leaves))
    return jax.tree.unflatten(
        treedef, [jax.random.fold_in(rng, i) for i in range(len(leaves))])


class GradCodec:
    """``none``: bitwise passthrough.  Base class of the real codecs —
    every method here delegates verbatim to :mod:`parallel.collectives`
    (or is the identity), so engines can call the codec unconditionally
    and the default compiles to exactly the pre-codec program."""

    name = "none"

    # ------------------------------------------------------------- payload
    def leaf_wire_bytes(self, shape, dtype) -> int:
        """Bytes this leaf occupies on the wire (one collective round)."""
        size = _numel(shape)
        return size * jnp.dtype(dtype).itemsize

    def wire_bytes(self, leaves: Iterable[Any]) -> int:
        """Total wire payload of one collective round over ``leaves``
        (anything with ``.shape``/``.dtype`` — concrete or abstract)."""
        return int(sum(self.leaf_wire_bytes(a.shape, a.dtype)
                       for a in leaves))

    # --------------------------------------------------------- collectives
    def all_reduce_sum(self, tree: PyTree, axis: str, *, rng=None) -> PyTree:
        del rng
        return coll.all_reduce_sum(tree, axis)

    def all_reduce_mean(self, tree: PyTree, axis: str, *, rng=None) -> PyTree:
        del rng
        return coll.all_reduce_mean(tree, axis)

    def neighbor_mean(self, tree: PyTree, axis: str, degree: int = 1, *,
                      rng=None) -> PyTree:
        del rng
        return coll.neighbor_mean(tree, axis, degree)

    # ----------------------------------------------------- GSPMD roundtrip
    def roundtrip(self, tree: PyTree, *, rng=None) -> PyTree:
        """Quantize→dequantize each leaf in place (no collective): the
        numerics of a compressed exchange for engines whose collective is
        compiler-inserted.  Identity here."""
        del rng
        return tree


class Bf16Codec(GradCodec):
    """Cast to bfloat16 for the exchange; the collective — including the
    ring reduction's in-flight additions — runs in bf16, and the result
    is widened back to float32 only after it.

    Only floating leaves wider than 2 bytes are cast; anything already
    bf16/f16 (or integral) passes through at its own width."""

    name = "bf16"

    @staticmethod
    def _compressible(dtype) -> bool:
        dtype = jnp.dtype(dtype)
        return (jnp.issubdtype(dtype, jnp.floating)
                and dtype.itemsize > 2)

    def leaf_wire_bytes(self, shape, dtype) -> int:
        size = _numel(shape)
        if self._compressible(dtype):
            return size * 2
        return size * jnp.dtype(dtype).itemsize

    def _through(self, tree, fn):
        """Run ``fn`` on the bf16 rendering of each compressible leaf; the
        collective inside ``fn`` then moves (and accumulates) bf16 — the
        wire dtype IS the compressed dtype — and the result is widened
        back to the leaf's original dtype."""
        def leaf(x):
            if self._compressible(x.dtype):
                return fn(x.astype(jnp.bfloat16)).astype(x.dtype)
            return fn(x)

        return jax.tree.map(leaf, tree)

    def all_reduce_sum(self, tree, axis, *, rng=None):
        del rng
        return self._through(tree, lambda x: lax.psum(x, axis_name=axis))

    def all_reduce_mean(self, tree, axis, *, rng=None):
        del rng
        return self._through(tree, lambda x: lax.pmean(x, axis_name=axis))

    def neighbor_mean(self, tree, axis, degree=1, *, rng=None):
        del rng
        return self._through(
            tree, lambda x: coll.neighbor_mean(x, axis, degree))

    def roundtrip(self, tree, *, rng=None):
        del rng
        return self._through(tree, lambda x: x)


def _int8_encode(x: jax.Array, rng) -> tuple[jax.Array, jax.Array]:
    """(q, scale): per-leaf max-abs scale, values stochastically rounded
    to int8 in [-127, 127].  With ``rng`` the rounding is stochastic —
    E[q·scale] == x exactly (floor(v + u), u ~ U[0,1)) — so quantization
    noise averages out across devices/steps instead of biasing the
    descent direction; without ``rng`` it rounds to nearest."""
    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32)) / 127.0
    scale = jnp.maximum(scale, jnp.finfo(jnp.float32).tiny)
    v = x32 / scale
    if rng is None:
        q = jnp.round(v)
    else:
        q = jnp.floor(v + jax.random.uniform(rng, x.shape, jnp.float32))
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def _int8_decode(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def int8_channel_encode(x: jax.Array,
                        axis: int = -1) -> tuple[jax.Array, jax.Array]:
    """(q, scale): max-abs scale reduced over ``axis`` (one f32 scale per
    remaining index), values rounded TO NEAREST into int8 [-127, 127].

    The serving KV cache's quantizer (serving/kv_cache.py kv_dtype=int8):
    each written K/V vector gets its own scale — per slot × position ×
    head, reduced over head_dim — so a write never has to requantize
    older cache entries, and decoding is deterministic (no stochastic
    rounding: a served token stream must be a pure function of the
    params + prompt, the same rule as greedy sampling)."""
    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32), axis=axis) / 127.0
    scale = jnp.maximum(scale, jnp.finfo(jnp.float32).tiny)
    q = jnp.clip(jnp.round(x32 / jnp.expand_dims(scale, axis)),
                 -127, 127).astype(jnp.int8)
    return q, scale


def int8_channel_decode(q: jax.Array, scale: jax.Array, dtype,
                        axis: int = -1) -> jax.Array:
    """Inverse of :func:`int8_channel_encode` (broadcasts the scale back
    over ``axis``)."""
    return (q.astype(jnp.float32)
            * jnp.expand_dims(scale, axis).astype(jnp.float32)).astype(dtype)


class Int8Codec(GradCodec):
    """Per-leaf scale + stochastic rounding to int8; f32 master values
    preserved (only the exchanged copy is quantized).

    The reduce is the standard two-phase compressed allreduce (the
    1-bit-SGD-lineage layout): each leaf is split into one chunk per
    device; phase 1 quantizes the local value and ``all_to_all``s the
    int8 chunks so device *i* can sum everyone's dequantized chunk *i*
    (per-device scales ride a scalar all-gather, so Σ qⱼ·sⱼ keeps each
    sender's scale exact — an int8-domain sum would need one global
    scale and would overflow at 8 summands); phase 2 re-quantizes the
    reduced chunk and ``all_gather``s it back.  Both phases move int8, so
    per-device traffic is ~2·(n-1)/n · size/4 bytes — the uncompressed
    ring allreduce's bandwidth shape at ¼ the bytes, at ANY device count
    (a naive gather-of-everything would scale received bytes with n and
    lose the win beyond n=8).  Transient memory is one extra f32 copy of
    the leaf (the (n, size/n) dequant buffer).  The reduced value passes
    through TWO stochastic roundings (each unbiased, so the composition
    is too); decoded error per element is bounded by Σⱼ sⱼ + s₂ — one
    quantum per sender plus one for the re-quantized sum."""

    name = "int8"

    @staticmethod
    def _compressible(dtype) -> bool:
        dtype = jnp.dtype(dtype)
        return jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize > 1

    def leaf_wire_bytes(self, shape, dtype) -> int:
        size = _numel(shape)
        if self._compressible(dtype):
            return size + 4  # int8 payload + one f32 scale per leaf
        return size * jnp.dtype(dtype).itemsize

    def _reduce(self, tree, axis, rng, mean: bool):
        n = lax.axis_size(axis)

        def leaf(x, key):
            if not self._compressible(x.dtype):
                red = lax.pmean if mean else lax.psum
                return red(x, axis_name=axis)
            size = x.size
            m = -(-size // n)  # chunk length (ceil; zero-padded tail)
            flat = jnp.pad(x.reshape(-1).astype(jnp.float32),
                           (0, n * m - size))
            # phase 1: quantize the whole local leaf once (one scale),
            # all_to_all the int8 chunks — device i receives chunk i of
            # every sender
            q, s = _int8_encode(flat.reshape(n, m), key)
            qx = lax.all_to_all(q, axis_name=axis, split_axis=0,
                                concat_axis=0)               # (n, m) int8
            sg = lax.all_gather(s, axis_name=axis)           # (n,) f32
            chunk = (qx.astype(jnp.float32) * sg[:, None]).sum(axis=0)
            # phase 2: re-quantize the reduced chunk, share it back
            q2, s2 = _int8_encode(
                chunk, None if key is None else jax.random.fold_in(key, 1))
            qg = lax.all_gather(q2, axis_name=axis)          # (n, m) int8
            sg2 = lax.all_gather(s2, axis_name=axis)         # (n,) f32
            total = (qg.astype(jnp.float32) * sg2[:, None]).reshape(-1)
            total = total[:size].reshape(x.shape)
            if mean:
                total = total / n
            return total.astype(x.dtype)

        return jax.tree.map(leaf, tree, _leaf_rngs(tree, rng))

    def all_reduce_sum(self, tree, axis, *, rng=None):
        return self._reduce(tree, axis, rng, mean=False)

    def all_reduce_mean(self, tree, axis, *, rng=None):
        return self._reduce(tree, axis, rng, mean=True)

    def neighbor_mean(self, tree, axis, degree=1, *, rng=None):
        if degree <= 0:
            return tree
        n = lax.axis_size(axis)
        if 2 * degree + 1 >= n:
            # whole-ring neighborhood — same degenerate case as the
            # uncompressed mix (collectives.neighbor_mean)
            return self.all_reduce_mean(tree, axis, rng=rng)

        def leaf(x, key):
            if not self._compressible(x.dtype):
                return coll.neighbor_mean(x, axis, degree)
            q, s = _int8_encode(x, key)
            acc = _int8_decode(q, s, jnp.float32)
            for d in range(1, degree + 1):
                fwd = [(i, (i + d) % n) for i in range(n)]
                bwd = [(i, (i - d) % n) for i in range(n)]
                for perm in (fwd, bwd):
                    # neighbors receive the int8 rendering + scale — the
                    # ring hop moves 1 byte/param, like the reductions
                    qp = lax.ppermute(q, axis_name=axis, perm=perm)
                    sp = lax.ppermute(s, axis_name=axis, perm=perm)
                    acc = acc + _int8_decode(qp, sp, jnp.float32)
            return (acc / (2 * degree + 1)).astype(x.dtype)

        return jax.tree.map(leaf, tree, _leaf_rngs(tree, rng))

    def roundtrip(self, tree, *, rng=None):
        def leaf(x, key):
            if not self._compressible(x.dtype):
                return x
            q, s = _int8_encode(x, key)
            return _int8_decode(q, s, x.dtype)

        return jax.tree.map(leaf, tree, _leaf_rngs(tree, rng))


_CODEC_CLASSES = {c.name: c for c in (GradCodec, Bf16Codec, Int8Codec)}


def codec_active(codec: GradCodec) -> bool:
    """True when the codec changes the collective program: a real
    compression codec, or the 'none' passthrough wrapped in bucketing
    (parallel/overlap.BucketedCodec — bucketed-none still replaces the
    monolithic exchange with per-bucket collectives the latency-hiding
    scheduler can overlap).  Engines branch on this instead of
    ``codec.name != 'none'`` wherever bucketing alone must activate the
    explicit-collective step."""
    return codec.name != "none" or bool(getattr(codec, "bucketed", False))


def make_codec(compression: str | GradCodec | None) -> GradCodec:
    """Resolve a ``--grad-compression`` value (or a ready codec instance)
    to a :class:`GradCodec`."""
    if compression is None:
        return GradCodec()
    if isinstance(compression, GradCodec):
        return compression
    try:
        return _CODEC_CLASSES[compression]()
    except KeyError:
        raise ValueError(
            f"unknown grad_compression '{compression}'; "
            f"known: {', '.join(CODECS)}") from None
