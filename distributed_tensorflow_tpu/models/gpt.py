"""GPT-style decoder-only causal language model.

The reference has no language models at all (SURVEY.md §2.2: its only model
is an MLP on 28×28, reference initializer.py:14-19) — this is TPU-native new
capability completing the model-family story: the framework's long-context
machinery (Pallas flash attention, ring/Ulysses sequence parallelism) exists
for exactly this workload, and a decoder LM is the model that exercises the
causal paths end-to-end (BERT only ever runs them non-causally).

Architecture: pre-LN transformer decoder (the trainable-at-depth variant),
learned positional embeddings, weight-tied LM head (`nn.Embed.attend`) —
tying keeps the biggest matrix single-copy in HBM and is standard for GPT-2
class models.  Logits are (B, L, V) for next-token prediction; the engines'
loss/eval broadcast over label dims (engines/base.py `cross_entropy`,
`token_weights`), so the same SyncEngine/FSDP/TP machinery that trains
classifiers trains this LM with zero engine-side special cases.

Attention is pluggable exactly like BERT (models/bert.py) but always causal:
  'dense'      — full causal attention; any mesh.
  'flash'      — Pallas flash kernel (ops/flash_attention.py), causal=True:
                 the kernel skips entirely-future blocks (~2× FLOPs saved)
                 and never materializes (L, L) scores in HBM.
  'ring'       — causal ring attention over the 'seq' mesh axis (inside
                 shard_map; engines/seq_parallel.py).
  'ring_flash' — ring schedule with flash local math: entirely-future
                 blocks never even launch a kernel.
  'ulysses'    — all-to-all head-parallel, causal.

``partition_model=True`` adds the same Megatron GSPMD annotations as BERT
(models/bert.py:28-34): QKV column-parallel, attention out + FFN-down
row-parallel, FFN-up column-parallel, token embedding vocab-sharded.  With
the tied head, `attend`'s contraction against the vocab-sharded embedding
makes the logits vocab-sharded too — XLA keeps the (B, L, V) tensor
distributed through the softmax-cross-entropy, never gathering V onto one
device (the Megatron vocab-parallel-loss layout, for free from GSPMD).
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_tpu.models.slot_contract import SlotContract
from distributed_tensorflow_tpu.ops.slot_attention import (
    prefix_rows, slot_decode_attention, takes_kernel)
from distributed_tensorflow_tpu.parallel import collectives as coll
from distributed_tensorflow_tpu.parallel import compression
from distributed_tensorflow_tpu.parallel import mesh as meshlib
from distributed_tensorflow_tpu.parallel.ring_attention import (
    dense_attention, ring_attention, ring_flash_attention,
    ulysses_attention, ulysses_flash_attention)


def _part(init, spec, enabled: bool):
    """Megatron annotation, applied only when TP-partitioned (mirrors
    models/bert.py:48-52: unannotated modules keep plain initializers so
    non-GSPMD engines see ordinary unboxed params)."""
    return nn.with_partitioning(init, spec) if enabled else init


def write_slot_rows(table, update, pos):
    """``table[b, pos[b, j]] = update[b, j]`` for every slot row ``b`` and
    block position ``j``: how a monolithic slot table takes a block of new
    K/V rows (and, under int8 storage, their scales).

    ``table`` is ``(slots, max_len, ...)``, ``update`` ``(slots, L, ...)``
    in the table's dtype, ``pos`` ``(slots, L)``.  One scatter in the form
    ``jax.vmap`` gives a per-slot ``lax.dynamic_update_slice``: the slot
    axis is a batching dimension and the update window is one whole
    position in the table's own shape, nothing collapsed.  The TPU
    compiler turns that form into a loop of in-place
    ``dynamic-update-slice``, one a slot, in whatever layout the table
    has; the two-index ``.at[rows, pos].set`` it served by re-laying the
    whole table out and back (on the v5e two copies of every leaf a step,
    3.2x padded: tests/test_tpu_compile.py holds that they stay away).

    WHO CALLS IT: the token-block / int8 branch of the slot-decode
    attention below (speculative verify, ``kv_dtype="int8"``: L
    positions, or four leaves of two dtypes), ``models/mla_moe.py`` and
    ``models/hybrid_ssm.py`` (latent and few-head tables whose one row is
    contiguous on the chip: the loop is a twentieth of their round or
    less, where a pass that rewrites the table would cost more).  The
    one-token branch of GPT-2's ``(slots, max_len, heads, head_dim)``
    table does NOT: there the v5e keeps ``max_len`` minor, one row is
    ``heads * head_dim`` single lanes in as many tiles, and the loop was
    nearly half of the round (``ops/slot_attention.py``; PERF.md
    section 6).

    DROP RULE: a position outside ``[0, max_len)`` leaves the table as it
    was — pad rows of a chunk bucket that run past the table, and a freed
    slot frozen at ``max_len``, rely on it.  It is the scatter's own
    out-of-bounds rule (``FILL_OR_DROP``; ``dynamic_update_slice`` itself
    would clamp and overwrite position ``max_len - 1``)."""
    n = table.ndim
    dnums = lax.ScatterDimensionNumbers(
        update_window_dims=tuple(range(2, n + 1)), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(1,), operand_batching_dims=(0,),
        scatter_indices_batching_dims=(0,))
    return lax.scatter(table, pos[..., None], update[:, :, None], dnums,
                       mode=lax.GatherScatterMode.FILL_OR_DROP)


def apply_rope(x, pos, base: float = 10000.0):
    """Rotary position embedding over the head dim (half-split layout).

    ``x``: (B, L, H, D) with D even; ``pos``: (B, L) or (1, L) absolute
    positions.  Rotation is a per-position preprocessing of q/k, so it
    composes unchanged with every attention impl — dense, the Pallas flash
    kernel, and the ring/Ulysses schedules (whose blocks receive globally
    offset positions) — and with the KV cache (the cached k is stored
    already rotated at its own position)."""
    d2 = x.shape[-1] // 2
    inv = base ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = pos.astype(jnp.float32)[..., None] * inv        # (B, L, D/2)
    cos = jnp.cos(ang)[:, :, None, :]                     # (B, L, 1, D/2)
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :d2].astype(jnp.float32), x[..., d2:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


class CausalSelfAttention(nn.Module):
    """Multi-head causal self-attention with pluggable block math."""

    hidden: int = 128
    heads: int = 4
    attention_impl: str = "dense"
    seq_axis: str = "seq"
    partition_model: bool = False
    decode: bool = False       # KV-cache mode: one token in, attend against
                               # everything cached (see ``generate``)
    max_len: int = 512         # cache capacity in decode mode
    rope: bool = False         # rotate q/k by position (RoPE) — requires
                               # the caller to pass ``pos``
    kv_heads: int | None = None  # GQA: K/V head count < query heads
                               # (None = heads, standard MHA; 1 = MQA).
                               # Shrinks the decode cache by heads/kv_heads.
    dtype: jnp.dtype = jnp.float32
    decode_slots: bool = False   # serving mode: the batch dim is a SLOT
                               # table (serving/kv_cache.py) — the caller
                               # passes per-slot write positions, cache
                               # writes go row by row
                               # (ops/slot_attention, write_slot_rows),
                               # and validity is length-driven, so one
                               # compiled decode step advances slots of
                               # any age
    kv_quant: bool = False     # int8 KV storage (decode_slots only): K/V
                               # cached as int8 with one f32 max-abs scale
                               # per written vector (slot × position ×
                               # head; parallel/compression.py channel
                               # quantizer), dequantized on the attention
                               # read — the stored table is what shrinks
    slot_mesh: Any = None      # the serving mesh when the slot table
                               # shards over its 'data' axis: the one-
                               # token step then takes its plain form
                               # (GSPMD partitions the program, and a
                               # Mosaic kernel cannot be partitioned)

    @nn.compact
    def __call__(self, x, pos=None, prefill: bool = False, active=None):
        head_dim = self.hidden // self.heads
        tp = self.partition_model
        if self.rope and pos is None:
            raise ValueError("rope=True needs the caller to pass positions")
        kvh = self.kv_heads if self.kv_heads is not None else self.heads
        if kvh < 1 or self.heads % kvh:
            raise ValueError(
                f"kv_heads must be a positive divisor of heads "
                f"{self.heads}, got {kvh}")

        # column-parallel QKV (packed output dim sharded over 'model');
        # plain Dense for the same partial-manual-shard_map reason as BERT
        # (models/bert.py:73-76).  Under GQA the K/V projections emit
        # kv_heads — the parameter and (cached) activation saving — and the
        # heads broadcast back to query count right before the attention
        # math (post-cache, so the cache stays small).
        def proj(name, n_heads):
            h = nn.Dense(
                n_heads * head_dim, dtype=self.dtype, name=name,
                kernel_init=_part(nn.initializers.lecun_normal(),
                                  (None, meshlib.MODEL_AXIS), tp),
                bias_init=_part(nn.initializers.zeros_init(),
                                (meshlib.MODEL_AXIS,), tp))(x)
            return h.reshape(h.shape[:-1] + (n_heads, head_dim))

        q = proj("query", self.heads)
        k, v = proj("key", kvh), proj("value", kvh)
        if self.rope:
            q, k = apply_rope(q, pos), apply_rope(k, pos)

        def widen(t):
            """kv_heads → heads by group broadcast (no-op for MHA)."""
            if kvh == self.heads:
                return t
            return jnp.repeat(t, self.heads // kvh, axis=2)
        if self.decode:
            # append this step's K/V at the cache cursor, attend q against
            # the whole cache with a validity mask — O(max_len) per token
            # instead of O(L²) re-prefill.  The cursor is causal masking:
            # positions past it are NEG_INF'd, so no triangular mask needed.
            # CONTRACT: at most max_len tokens total.  The cursor is a
            # traced value, so overflow cannot raise here — past capacity,
            # dynamic_update_slice clamps and the newest token overwrites
            # slot max_len-1.  `generate` (the supported entry) checks
            # prompt+max_new_tokens against max_len eagerly; direct
            # decode-API users get a sticky ``cache['overflow']`` flag
            # (ADVICE r3: the silent clamp corrupted continuations with no
            # signal) — check it after the decode loop.
            if x.shape[1] != 1 and not self.decode_slots:
                raise ValueError(
                    f"decode mode consumes one token per call, got "
                    f"sequence length {x.shape[1]}")
            if self.kv_quant and not self.decode_slots:
                raise ValueError(
                    "kv_quant=True is a slot-table storage mode: it "
                    "requires decode_slots=True (the serving engine owns "
                    "the quantized table)")
            b = x.shape[0]
            if self.decode_slots:
                # SLOT decode (serving/kv_cache.py): each batch row is an
                # independent slot with its own age.  The write index is
                # the caller-supplied per-slot position (= the slot's
                # current length), the write ``ops/slot_attention`` for
                # one token and ``write_slot_rows`` for a block or int8 (both
                # leave the table in its own layout; a position at or
                # past max_len is DROPPED there, not clamped), and the
                # validity mask length-driven — so the SAME compiled step
                # advances a slot mid-prefill-history and a slot hundreds
                # of tokens deep at once.  No cursor/overflow variables:
                # positions are external state owned by the serving
                # engine, which guards capacity at admission time
                # (prompt + max_new_tokens ≤ max_len — the host-side
                # twin of the scalar path's sticky overflow flag).
                # CHUNK-RESUME CONTRACT: because the position is caller-
                # supplied and validity is derived from it alone, prefill
                # may stop at any position and resume later (chunked
                # prefill) or start PAST zero over externally-written KV
                # (a prefix-cache hit restores blocks 0..p-1 and resumes
                # at p) — the per-token math is identical either way,
                # which is what makes chunked admission bitwise equal at
                # every chunk budget, and equal in tokens to the block
                # prefill below (tests/test_serving.py).
                # TOKEN-BLOCK CONTRACT (speculative verify): the same
                # mode also accepts a (B, L) block of L consecutive
                # tokens per slot — all L K/V vectors are written into the
                # cache first, then each query attends under a PER-QUERY
                # validity mask (positions ≤ its own), so position j's
                # logits condition on exactly the block prefix 0..j plus
                # the cache: one batched step scores k draft tokens + the
                # committed token, and rejected positions are invalidated
                # by length bookkeeping alone
                # (serving/kv_cache.py verify_block).
                # MULTI-STEP CONTRACT (fused k-iteration decode,
                # serving/kv_cache.py advance_multi): a lax.scan drives
                # this same step k times with token feedback on device,
                # freezing each slot's position once it deactivates
                # (EOS/budget) — a deactivated row keeps writing its
                # stale token at the SAME frozen position every
                # remaining iteration.  That rewrite is safe by the two
                # properties already stated above: the write is
                # per-(row, position) so it only ever touches the one
                # cell past the frozen length (none at all once that is
                # max_len: the drop rule), and validity is derived
                # from the caller's length vector alone, so the junk
                # cell is invisible to attention until a real token
                # advances the length and overwrites it first.  The
                # ``active`` mask (the single step's and the scan's
                # carried one alike) only bounds the prefix the one-token
                # form reads (ops/slot_attention.py THE RULE): the same
                # slots advance under the same rule in both programs,
                # which keeps the fused one identical to k calls of the
                # single step (the bitwise-parity pin in
                # tests/test_serving_multistep.py).
                # BLOCK-PREFILL CONTRACT (``prefill=True``;
                # serving/kv_cache.py ``insert``): the (1, L) block is a
                # padded prompt from position 0, so nothing valid lies in
                # the table below it and the table is not read: the block
                # attends causally to its own q/k/v, and its K/V (+ scales)
                # land in the slot's rows [0, L) as one contiguous piece.
                # Pad tokens sit after the prompt: no real query sees one,
                # and their rows are beyond the slot's length.
                if pos is None:
                    raise ValueError(
                        "decode_slots=True needs per-slot positions "
                        "(B, 1) — the serving engine passes the slot "
                        "length vector")
                ready = self.has_variable("cache", "cached_key")
                store = jnp.int8 if self.kv_quant else self.dtype
                ck = self.variable(
                    "cache", "cached_key", jnp.zeros,
                    (b, self.max_len, kvh, head_dim), store)
                cv = self.variable(
                    "cache", "cached_value", jnp.zeros,
                    (b, self.max_len, kvh, head_dim), store)
                if self.kv_quant:
                    # one f32 max-abs scale per written K/V vector (slot
                    # × position × head), stored alongside the table in
                    # the same cache pytree — the slot dim shards
                    # identically (parallel/mesh.kv_slot_sharding handles
                    # the 3-dim leaf), and a write never requantizes
                    # older entries
                    ks = self.variable(
                        "cache", "key_scale", jnp.zeros,
                        (b, self.max_len, kvh), jnp.float32)
                    vs = self.variable(
                        "cache", "value_scale", jnp.zeros,
                        (b, self.max_len, kvh), jnp.float32)
                if not ready:
                    out = dense_attention(q, widen(k), widen(v),
                                          causal=True)
                elif prefill:
                    # L <= max_len always, so no drop rule is needed; not
                    # ``write_slot_rows``, which on the v5e is a loop of
                    # one dynamic-update-slice a row
                    rows = [(ck, k), (cv, v)]
                    if self.kv_quant:
                        qk, sk = compression.int8_channel_encode(k)
                        qv, sv = compression.int8_channel_encode(v)
                        rows = [(ck, qk), (cv, qv), (ks, sk), (vs, sv)]
                    for table, new in rows:
                        table.value = lax.dynamic_update_slice_in_dim(
                            table.value, new.astype(table.value.dtype), 0,
                            axis=1)
                    out = dense_attention(q, widen(k), widen(v),
                                          causal=True)
                elif x.shape[1] == 1 and not self.kv_quant:
                    # the one-token step (kv_decode_step, and the scans of
                    # advance_multi and the chunked prefill), one form for
                    # all three (ops/slot_attention.py): on the TPU a
                    # kernel that reads the prefix the ``active`` slots
                    # need and writes back the one block holding each new
                    # row; the plain full-table select elsewhere, and for
                    # a mesh or tensor-parallel heads (a Mosaic kernel is
                    # not partitioned).  The row is cast to the table's
                    # dtype (SlotKVCache kv_dtype — bf16 halves KV memory)
                    # and a position outside the table is dropped.
                    ck.value, cv.value, out = slot_decode_attention(
                        q, k, v, ck.value, cv.value, pos, active,
                        widen=widen, plain=(self.partition_model
                                            or self.slot_mesh is not None))
                else:
                    # token-block write (speculative verify) and/or int8
                    # storage: write every position's K/V (+ scale) with
                    # ``write_slot_rows`` (no cell serves this branch; a
                    # select over L positions or four leaves is not the
                    # branch above's one fused pass), then attend each
                    # query against the table under its own position mask
                    # — the L == 1 case of this path is the same math as
                    # that branch and leaves the same table
                    idx = pos                       # (B, L)
                    if self.kv_quant:
                        qk, sk = compression.int8_channel_encode(k)
                        qv, sv = compression.int8_channel_encode(v)
                        ck.value = write_slot_rows(ck.value, qk, idx)
                        cv.value = write_slot_rows(cv.value, qv, idx)
                        ks.value = write_slot_rows(ks.value, sk, idx)
                        vs.value = write_slot_rows(vs.value, sv, idx)
                        keys = compression.int8_channel_decode(
                            ck.value, ks.value, self.dtype)
                        vals = compression.int8_channel_decode(
                            cv.value, vs.value, self.dtype)
                    else:
                        ck.value = write_slot_rows(
                            ck.value, k.astype(ck.value.dtype), idx)
                        cv.value = write_slot_rows(
                            cv.value, v.astype(cv.value.dtype), idx)
                        keys, vals = ck.value, cv.value
                    valid = (jnp.arange(self.max_len)[None, None, :]
                             <= idx[:, :, None]).astype(self.dtype)
                    out = dense_attention(
                        q, widen(keys), widen(vals),
                        causal=False, kv_mask=valid)
                out = out.reshape(out.shape[:-2]
                                  + (self.heads * head_dim,))
                # same name="out" as the shared projection below: only one
                # branch ever executes, so the param tree stays identical
                # to every other mode — a training checkpoint serves as-is
                return nn.Dense(
                    self.hidden, dtype=self.dtype, name="out",
                    kernel_init=_part(nn.initializers.lecun_normal(),
                                      (meshlib.MODEL_AXIS, None), tp))(out)
            # has_variable is False exactly during .init(): create the cache
            # zeros but do NOT write/advance — init-time mutations persist
            # into the returned variables, which would hand `generate` a
            # cache already holding the dummy init token (cursor at 1)
            ready = self.has_variable("cache", "cached_key")
            ck = self.variable(
                "cache", "cached_key", jnp.zeros,
                (b, self.max_len, kvh, head_dim), self.dtype)
            cv = self.variable(
                "cache", "cached_value", jnp.zeros,
                (b, self.max_len, kvh, head_dim), self.dtype)
            cur = self.variable("cache", "cache_index",
                                lambda: jnp.zeros((), jnp.int32))
            ovf = self.variable("cache", "overflow",
                                lambda: jnp.zeros((), jnp.bool_))
            if not ready:
                out = dense_attention(q, widen(k), widen(v), causal=True)
            else:
                i = cur.value
                # sticky overflow marker: True once a token would land past
                # capacity (dynamic_update_slice is about to clamp)
                ovf.value = ovf.value | (i >= self.max_len)
                ck.value = jax.lax.dynamic_update_slice(
                    ck.value, k, (0, i, 0, 0))
                cv.value = jax.lax.dynamic_update_slice(
                    cv.value, v, (0, i, 0, 0))
                cur.value = i + 1
                valid = (jnp.arange(self.max_len) <= i).astype(self.dtype)
                out = dense_attention(
                    q, widen(ck.value), widen(cv.value), causal=False,
                    kv_mask=jnp.broadcast_to(valid[None, :],
                                             (b, self.max_len)))
        elif self.attention_impl == "ring":
            out = ring_attention(q, widen(k), widen(v), axis=self.seq_axis,
                                 causal=True)
        elif self.attention_impl == "ring_flash":
            out = ring_flash_attention(q, widen(k), widen(v),
                                       axis=self.seq_axis, causal=True)
        elif self.attention_impl == "ulysses":
            out = ulysses_attention(q, widen(k), widen(v),
                                    axis=self.seq_axis, causal=True)
        elif self.attention_impl == "ulysses_flash":
            out = ulysses_flash_attention(q, widen(k), widen(v),
                                          axis=self.seq_axis, causal=True)
        elif self.attention_impl == "flash":
            from distributed_tensorflow_tpu.ops import flash_attention
            out = flash_attention(q, widen(k), widen(v), causal=True)
        else:
            out = dense_attention(q, widen(k), widen(v), causal=True)
        out = out.reshape(out.shape[:-2] + (self.heads * head_dim,))
        # row-parallel output projection — the pair's single all-reduce
        return nn.Dense(
            self.hidden, dtype=self.dtype, name="out",
            kernel_init=_part(nn.initializers.lecun_normal(),
                              (meshlib.MODEL_AXIS, None), tp))(out)


class GPTBlock(nn.Module):
    """Pre-LN decoder block: x + attn(LN(x)); x + ffn(LN(x)).

    ``moe_experts > 0`` swaps the dense FFN for a routed MoE layer
    (models/moe.py MoELayer) over the block's tokens — the long-context
    MoE shape: under sequence parallelism each seq device routes its own
    token block to the globally-sharded experts (the dispatch einsums stay
    GSPMD over 'expert' while 'seq' is a manual shard_map axis,
    engines/composite.py).  The router's aux/z losses and overflow sow
    into ``intermediates`` exactly as in MoEClassifier."""

    hidden: int = 128
    heads: int = 4
    ffn: int = 512
    dropout_rate: float = 0.1
    attention_impl: str = "dense"
    seq_axis: str = "seq"
    partition_model: bool = False
    decode: bool = False
    max_len: int = 512
    rope: bool = False
    kv_heads: int | None = None
    dtype: jnp.dtype = jnp.float32
    moe_experts: int = 0         # 0 = dense FFN; >0 = routed experts
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    partition_experts: bool = False
    decode_slots: bool = False   # serving slot-table decode (see attention)
    kv_quant: bool = False       # int8 KV storage (see attention)
    slot_mesh: Any = None        # the serving mesh (see attention)

    @nn.compact
    def __call__(self, x, train: bool = False, pos=None,
                 prefill: bool = False, active=None):
        tp = self.partition_model
        y = CausalSelfAttention(self.hidden, self.heads, self.attention_impl,
                                self.seq_axis, tp, self.decode, self.max_len,
                                self.rope, self.kv_heads, self.dtype,
                                decode_slots=self.decode_slots,
                                kv_quant=self.kv_quant,
                                slot_mesh=self.slot_mesh)(
                                    nn.LayerNorm(dtype=self.dtype)(x), pos,
                                    prefill, active)
        y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        x = x + y
        y = nn.LayerNorm(dtype=self.dtype)(x)
        if self.moe_experts > 0:
            from distributed_tensorflow_tpu.models.moe import moe_ffn

            y = moe_ffn(y, hidden=self.ffn, moe_experts=self.moe_experts,
                        moe_top_k=self.moe_top_k,
                        moe_capacity_factor=self.moe_capacity_factor,
                        partition_experts=self.partition_experts,
                        partition_model=tp, dtype=self.dtype)
        else:
            # Megatron FFN: column-parallel up, row-parallel down
            y = nn.Dense(
                self.ffn, dtype=self.dtype,
                kernel_init=_part(nn.initializers.lecun_normal(),
                                  (None, meshlib.MODEL_AXIS), tp),
                bias_init=_part(nn.initializers.zeros_init(),
                                (meshlib.MODEL_AXIS,), tp))(y)
            y = nn.gelu(y)
            y = nn.Dense(
                self.hidden, dtype=self.dtype,
                kernel_init=_part(nn.initializers.lecun_normal(),
                                  (meshlib.MODEL_AXIS, None), tp))(y)
        y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        return x + y


class GPTLM(nn.Module):
    """Decoder-only causal LM: token ids (B, L) → next-token logits (B, L, V).

    ``causal_lm = True`` is the marker the harness/engines read to route
    LM-shaped labels ((B, L) targets sharded over data AND seq axes,
    engines/seq_parallel.py) — the model itself never shifts anything; the
    dataset supplies (inputs, next-token targets) pairs (data/loaders.py
    ``lm_synth``).
    """

    vocab_size: int = 256
    hidden: int = 128
    layers: int = 2
    heads: int = 4
    ffn: int = 512
    max_len: int = 512
    dropout_rate: float = 0.1
    attention_impl: str = "dense"
    seq_axis: str = "seq"
    partition_model: bool = False
    decode: bool = False       # KV-cache autoregressive mode (see `generate`)
    positional: str = "learned"  # learned | rope (rotary: no position
                                 # table; q/k rotated by absolute position
                                 # in every attention layer)
    kv_heads: int | None = None  # GQA/MQA: K/V heads < query heads
    tie_embeddings: bool = True
    moe_experts: int = 0         # >0: every block's FFN is a routed MoE
                                 # layer (models/moe.py) — the long-context
                                 # MoE shape; composes with ring/Ulysses
                                 # seq parallelism (engines/composite.py
                                 # ep×sp: experts GSPMD-sharded over
                                 # 'expert' while 'seq' stays manual)
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    partition_experts: bool = False
    remat: bool = False          # activation checkpointing: store only each
                                 # block's INPUT, recompute the block in
                                 # backward — activation memory drops from
                                 # O(layers · per-block intermediates) to
                                 # O(layers · hidden) + one block's
                                 # intermediates, at ~1/3 extra FLOPs.  The
                                 # long-context lever: composes with
                                 # ring/Ulysses seq parallelism (the ring's
                                 # ppermutes replay symmetrically on every
                                 # seq device during recompute).
    dtype: jnp.dtype = jnp.float32
    decode_slots: bool = False   # serving: the batch dim is a SLOT table
                                 # (serving/kv_cache.py) — the caller passes
                                 # per-slot ``positions`` and owns the
                                 # length/active bookkeeping; one compiled
                                 # decode step advances slots of any age
    kv_quant: bool = False       # int8 KV storage with per-vector f32
                                 # scales (decode_slots only; --serve-kv-
                                 # dtype int8 — the stored table is ~¼ of
                                 # f32, ~½ of bf16)
    slot_mesh: Any = None        # the serving mesh (its 'data' axis
                                 # shards the slots; see attention)

    causal_lm = True  # read by engines/harness to select the LM data layout

    def slot_decode_clone(self, *, mesh=None,
                          kv_quant: bool = False) -> "GPTLM":
        """The module ``serving/kv_cache.SlotKVCache`` serves from: dense
        cache attention over a slot table, dropout off, the Megatron
        layout kept where the model was partitioned and ``mesh`` has a
        'model' axis."""
        tp = (self.partition_model and mesh is not None
              and meshlib.MODEL_AXIS in mesh.axis_names)
        return self.clone(decode=True, decode_slots=True,
                          attention_impl="dense", partition_model=tp,
                          dropout_rate=0.0, kv_quant=kv_quant,
                          slot_mesh=mesh)

    def slot_contract(self) -> SlotContract:
        """The slot step takes ``active`` (it reads the prefix the active
        slots need) and resumes from any position over per-head K/V rows,
        which SlotKVCache's chunk, pool, multi-step, verify and handoff
        programs are built from."""
        return SlotContract(
            takes_active=True, resumable_step=True,
            step_param_dtype=self.step_param_dtype,
            tensor_parallel=self.partition_model, int8_rows=True,
            round_attrs=self.slot_round_attrs)

    def slot_round_attrs(self, lengths, advancing) -> dict[str, int]:
        """``rows_read``: how many positions of every slot's rows the
        one-token step reads in a round that advances ``advancing`` at
        ``lengths`` (host arrays): the prefix of ops/slot_attention.py's
        rule where the step runs the kernel, the whole table where it runs
        the plain form."""
        plain = (self.kv_quant or self.partition_model
                 or self.slot_mesh is not None)
        if not takes_kernel(self.heads, self.kv_heads or self.heads,
                            self.hidden // self.heads, self.max_len,
                            plain=plain):
            return {"rows_read": self.max_len}
        return {"rows_read": prefix_rows(lengths, advancing, self.max_len)}

    def step_param_dtype(self, path: tuple[str, ...]):
        """The dtype in which a call first uses the parameter at ``path``
        (the tree's keys from the root to the leaf), or None where it
        uses the leaf as it is held.  ``serving/kv_cache.SlotKVCache``
        holds each leaf in that dtype, so that a float32 checkpoint
        served at bfloat16 is narrowed once and not in every program.

        flax's ``Dense`` and ``Embed`` convert kernel, bias and embedding
        to the module's ``dtype`` before the first use (``promote_dtype``),
        so converting beforehand gives the same bits wherever the compiler
        rounds where flax says (held by test on the CPU; the v5e's keeps a
        float32 kernel's extra bits inside some prefill programs: PERF.md
        section 6, PR 36).  ``LayerNorm``
        multiplies by ``scale`` and adds ``bias`` in float32 whatever its
        ``dtype`` and only then rounds: those leaves are not named, nor are
        the routed experts' (``models/moe.py`` holds and uses its own)."""
        leaf, module = path[-1], path[-2] if len(path) > 1 else ""
        if leaf in ("kernel", "embedding") or (
                leaf == "bias" and not module.startswith("LayerNorm")):
            return self.dtype
        return None

    @nn.compact
    def __call__(self, token_ids, train: bool = False, positions=None,
                 prompt_len=None, active=None):
        seq_parallel = self.attention_impl in ("ring", "ring_flash",
                                               "ulysses", "ulysses_flash")
        lq = token_ids.shape[1]
        # a slot prefill (serving/kv_cache.py ``insert``): the (1, L) block
        # is a padded prompt from position 0 whose first ``prompt_len``
        # tokens are real — the attention layers' block-prefill contract,
        # and logits for the last real position alone
        prefill = prompt_len is not None
        if prefill and not self.decode_slots:
            raise ValueError(
                "prompt_len marks a slot prefill from position 0: it "
                "requires decode_slots=True")
        if self.decode_slots and not self.decode:
            raise ValueError("decode_slots=True requires decode=True "
                             "(slot serving is a KV-cache decode mode)")
        if positions is not None and not self.decode_slots:
            raise ValueError(
                "positions is only accepted in decode_slots mode — every "
                "other mode derives positions internally (cursor/offset)")
        if self.decode:
            if seq_parallel:
                # the hard constraint: ring/ulysses run inside shard_map
                # with a manual 'seq' axis whose collectives assume every
                # device holds a full-length sequence block — a one-token
                # decode step has no seq dimension to shard, so there is
                # nothing for the ring to rotate.  Decode instead uses
                # dense cache attention; multi-device decode shards the
                # BATCH over 'data' and (optionally, GSPMD) the heads/vocab
                # over 'model' — see `generate(mesh=...)`.
                raise ValueError(
                    "decode mode is incompatible with sequence-parallel "
                    "attention (ring/ring_flash/ulysses run in shard_map "
                    "over 'seq'; a 1-token step has no sequence to shard); "
                    "clone with attention_impl='dense' — `generate` does "
                    "this.  partition_model decode IS supported (GSPMD).")
            if self.decode_slots:
                # serving: per-slot positions come from the caller (the
                # slot length vector) — there is no shared cursor because
                # slots are at different depths by construction
                if positions is None:
                    raise ValueError(
                        "decode_slots=True needs positions (B, L): the "
                        "per-slot write index / position-embedding input")
                if positions.shape != token_ids.shape:
                    raise ValueError(
                        f"positions shape {positions.shape} must match "
                        f"token_ids shape {token_ids.shape}")
                pos = positions
            else:
                # the model-level cursor feeds the position embedding; each
                # attention layer keeps its own cache cursor in lockstep.
                # Not advanced during .init() (same guard as the attention
                # cache).
                ready = self.has_variable("cache", "pos_index")
                pcur = self.variable("cache", "pos_index",
                                     lambda: jnp.zeros((), jnp.int32))
                pos = pcur.value + jnp.arange(lq)[None, :]
                if ready:
                    pcur.value = pcur.value + lq
        elif seq_parallel:
            if lq * coll.axis_size(self.seq_axis) > self.max_len:
                raise ValueError(
                    f"sequence length {lq * coll.axis_size(self.seq_axis)} "
                    f"exceeds max_len={self.max_len}")
            # this device's token block starts at global position idx×lq
            offset = coll.axis_index(self.seq_axis) * lq
            pos = offset + jnp.arange(lq)[None, :]
        else:
            if lq > self.max_len:
                raise ValueError(
                    f"sequence length {lq} exceeds max_len={self.max_len}; "
                    f"raise max_len or shorten the input")
            pos = jnp.arange(lq)[None, :]

        embed = nn.Embed(
            self.vocab_size, self.hidden, dtype=self.dtype,
            name="token_embed",
            embedding_init=_part(nn.linear.default_embed_init,
                                 (meshlib.MODEL_AXIS, None),
                                 self.partition_model))
        if self.positional not in ("learned", "rope"):
            raise ValueError(
                f"unknown positional '{self.positional}'; learned | rope")
        rope = self.positional == "rope"
        x = embed(token_ids)
        if not rope:
            x = x + nn.Embed(self.max_len, self.hidden, dtype=self.dtype,
                             name="pos_embed")(pos)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        # remat: train (arg 2) and prefill (arg 4) are static python bools;
        # x and pos trace.
        # The wrapped class is instantiated with an explicit name pinned to
        # the unwrapped auto-name ("GPTBlock_{i}") — nn.remat renames the
        # class, and flax derives both the param-tree path AND the init RNG
        # stream from the module path, so without the pin a remat=True model
        # would initialize *different* params under *different* paths
        # (breaking remat/non-remat grad parity and cross-flag checkpoint
        # restore).
        if self.remat and self.moe_experts:
            raise ValueError(
                "remat + MoE blocks is unsupported: the router's sown "
                "intermediates (aux_loss/z_loss/overflow) would be re-sown "
                "during backward recompute, double-counting the balance "
                "losses; train MoE blocks without --remat")
        block_cls = (nn.remat(GPTBlock, static_argnums=(2, 4))
                     if self.remat else GPTBlock)
        for i in range(self.layers):
            # slot decode threads pos regardless of rope: the attention
            # layer needs the per-slot write index, not just the rotation
            x = block_cls(self.hidden, self.heads, self.ffn,
                          self.dropout_rate, self.attention_impl,
                          self.seq_axis, self.partition_model,
                          self.decode, self.max_len, rope, self.kv_heads,
                          self.dtype, self.moe_experts, self.moe_top_k,
                          self.moe_capacity_factor, self.partition_experts,
                          decode_slots=self.decode_slots,
                          kv_quant=self.kv_quant,
                          slot_mesh=self.slot_mesh,
                          name=f"GPTBlock_{i}")(
                              x, train,
                              pos if (rope or self.decode_slots) else None,
                              prefill, active)
        if prefill:     # the one position whose logits sample a token
            x = jnp.take_along_axis(
                x, (prompt_len - 1)[:, None, None].astype(jnp.int32), axis=1)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        if self.tie_embeddings:
            # tied head: contraction against the (possibly vocab-sharded)
            # embedding — under TP the logits stay vocab-sharded through the
            # loss (Megatron vocab-parallel layout)
            logits = embed.attend(x)
        else:
            logits = nn.Dense(
                self.vocab_size, dtype=self.dtype, name="lm_head",
                kernel_init=_part(nn.initializers.lecun_normal(),
                                  (None, meshlib.MODEL_AXIS),
                                  self.partition_model))(x)
        return logits.astype(jnp.float32)


def generate(model: GPTLM, params, prompt, max_new_tokens: int, *,
             temperature: float = 1.0, greedy: bool = False, rng=None,
             mesh=None):
    """Autoregressive sampling with a KV cache: (B, Lp) prompt →
    (B, max_new_tokens) continuation.

    The inference counterpart the training framework would otherwise lack
    (no reference counterpart — the reference has no sequence models at
    all, SURVEY.md §2.2).  The model is cloned into decode mode (dense
    cache attention, dropout off); prompt tokens prefill the cache one at a
    time under `lax.scan`, then each new token costs one O(max_len)
    cache-attention step instead of an O(L²) re-prefill.  ``greedy=True``
    takes the argmax; otherwise tokens draw from
    ``softmax(logits / temperature)``.  Cache correctness is oracle-tested
    against teacher-forced full-forward rollout (tests/test_gpt.py).

    ``mesh`` enables multi-device decoding (GSPMD — the inference
    counterpart of the training-side parallelism):

    * the prompt batch and every cache leaf shard over the ``data`` axis
      (batch-parallel sampling: B must divide by the axis size);
    * with ``model.partition_model`` and a ``model`` mesh axis, params
      keep their Megatron layout — QKV/FFN matmuls stay head-sharded and
      the tied vocab-sharded head emits vocab-sharded logits whose
      argmax/categorical XLA resolves with its own collectives (TP
      decode).  Params already committed to the mesh (e.g. a TP engine's
      TrainState) are used in place; unsharded params replicate.
    * sequence-parallel attention cannot decode (see the in-model error:
      shard_map's manual 'seq' collectives need a sequence dimension a
      1-token step lacks) — ``generate`` always decodes with dense cache
      attention regardless of the training-time ``attention_impl``.

    Multi-device parity vs the single-device sampler is oracle-tested in
    tests/test_gpt.py.
    """
    import jax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    keep_tp = (mesh is not None and model.partition_model
               and meshlib.MODEL_AXIS in mesh.axis_names)
    dm = model.clone(decode=True, attention_impl="dense",
                     partition_model=keep_tp, dropout_rate=0.0)
    prompt = jnp.asarray(prompt)
    b, lp = prompt.shape
    if lp + max_new_tokens > model.max_len:
        raise ValueError(
            f"prompt ({lp}) + max_new_tokens ({max_new_tokens}) exceeds the "
            f"cache capacity max_len={model.max_len}")
    if rng is None:
        rng = jax.random.key(0)

    # fresh zero caches: shapes from an abstract init (eval_shape runs no
    # FLOPs — an eager dm.init here would pay a full unjitted forward pass
    # per generate call, dominating the cost the compiled-sampler cache
    # exists to avoid).  Every cache variable initializes to zeros, so
    # zeros-from-shape IS the init value.
    cache_shapes = jax.eval_shape(
        lambda: dm.init(jax.random.key(0), prompt[:, :1],
                        train=False))["cache"]
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes)

    if mesh is not None:
        if meshlib.DATA_AXIS in mesh.axis_names:
            dp = mesh.shape[meshlib.DATA_AXIS]
            if b % dp:
                raise ValueError(
                    f"batch {b} not divisible by the data axis ({dp})")
            batch_spec = P(meshlib.DATA_AXIS)
        else:
            batch_spec = P()
        prompt = jax.device_put(
            prompt, NamedSharding(mesh, P(*batch_spec, None)))
        # cache leaves are (B, ...) tensors (KV, cursors are scalars):
        # shard the batch dim, replicate scalars
        cache = jax.tree.map(
            lambda t: jax.device_put(
                t, NamedSharding(
                    mesh,
                    P(*batch_spec, *([None] * (t.ndim - 1)))
                    if t.ndim else P())),
            cache)
        # params committed to this mesh (TP TrainState) are used in place;
        # anything else replicates onto the mesh
        repl = NamedSharding(mesh, P())
        target_devices = mesh.devices.tolist()

        def place(t):
            sh = getattr(t, "sharding", None)
            if isinstance(sh, NamedSharding) and (
                    sh.mesh is mesh
                    or sh.mesh.devices.tolist() == target_devices):
                return t
            return jax.device_put(t, repl)

        params = jax.tree.map(place, params)
        rng = jax.device_put(rng, repl)

    run = _compiled_sampler(dm, max_new_tokens, bool(greedy),
                            float(temperature))
    return run(params, cache, prompt, rng)


@functools.lru_cache(maxsize=32)
def _compiled_sampler(dm: GPTLM, max_new_tokens: int, greedy: bool,
                      temperature: float):
    """One jitted prefill+decode program per (model config, length, mode).

    linen Modules are frozen dataclasses (hashable by field values), so the
    lru_cache makes repeated `generate` calls — per-eval-batch sampling
    loops — reuse the compiled scans instead of paying full XLA compilation
    on every call (params/cache/prompt are traced arguments, not closure
    constants)."""
    import jax
    from jax import lax

    def one(params, cache, tok):
        """(cache, (B,) token) → (cache, (B, V) logits for the NEXT pos)."""
        logits, upd = dm.apply({"params": params, "cache": cache},
                               tok[:, None], train=False, mutable=["cache"])
        return upd["cache"], logits[:, -1]

    @jax.jit
    def run(params, cache, prompt, rng):
        # prefill: all but the last prompt token (their logits are unused)
        cache, _ = lax.scan(lambda c, t: (one(params, c, t)[0], None),
                            cache, prompt[:, :-1].T)

        def gen(carry, _):
            cache, tok, rng = carry
            cache, logits = one(params, cache, tok)
            rng, sub = jax.random.split(rng)
            if greedy:
                nxt = logits.argmax(-1)
            else:
                nxt = jax.random.categorical(
                    sub, logits / max(temperature, 1e-6))
            nxt = nxt.astype(tok.dtype)
            return (cache, nxt, rng), nxt

        (_, _, _), toks = lax.scan(gen, (cache, prompt[:, -1], rng),
                                   None, length=max_new_tokens)
        return toks.T  # (B, max_new_tokens)

    return run


# --------------------------------------------------------------------------
# Pipeline stages (engines/pipeline.py `stages=` plug-in): embed → S
# identical GPTBlock stages → final-LN + untied LM head.  The head is untied
# by construction — the pipeline stacks stage params over 'pipe', so the
# embedding (stage 0's params) is not addressable from the head stage;
# weight tying across pipeline stages would need a cross-stage ppermute of
# the embedding every step, which costs more than the untied head it saves.
# Dropout-free, like the BERT stages (models/bert.py:233-240): the schedule
# re-applies stages every tick, so rng-consuming ops would draw
# inconsistent masks.
# --------------------------------------------------------------------------


class GPTPipeEmbed(nn.Module):
    """Input stage: token (+ learned position) embeddings; under RoPE the
    position table disappears and rotation happens inside each block.

    ``seq_axis`` set (pp×sp): the stage sees a seq-SHARDED token block, so
    learned positions offset by block index × local length (global
    positions, same as GPTLM's seq-parallel path)."""

    vocab_size: int = 256
    hidden: int = 128
    max_len: int = 512
    partition_model: bool = False
    rope: bool = False
    seq_axis: str | None = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, token_ids):
        lq = token_ids.shape[1]
        sp = coll.axis_size(self.seq_axis) if self.seq_axis else 1
        if lq * sp > self.max_len:
            raise ValueError(
                f"sequence length {lq * sp} exceeds max_len={self.max_len}")
        x = nn.Embed(
            self.vocab_size, self.hidden, dtype=self.dtype,
            embedding_init=_part(nn.linear.default_embed_init,
                                 (meshlib.MODEL_AXIS, None),
                                 self.partition_model))(token_ids)
        if self.rope:
            return x
        offset = (coll.axis_index(self.seq_axis) * lq if self.seq_axis
                  else 0)
        pos = offset + jnp.arange(lq)[None, :]
        return x + nn.Embed(self.max_len, self.hidden,
                            dtype=self.dtype)(pos)


class GPTPipeBlock(nn.Module):
    """One pipeline stage: ``layers_per_stage`` pre-LN decoder blocks.

    Without a ``seq_axis``, pipeline microbatches carry FULL sequences
    (only the batch splits), so RoPE positions are simply arange(L).  With
    ``seq_axis`` set (pp×sp), the carry is a seq-sharded token block:
    attention must be a sequence-parallel impl ('ring'/'ring_flash'/
    'ulysses') and RoPE positions offset to global."""

    hidden: int = 128
    heads: int = 4
    ffn: int = 512
    layers_per_stage: int = 1
    partition_model: bool = False
    rope: bool = False
    kv_heads: int | None = None
    attention_impl: str = "dense"
    seq_axis: str | None = None
    dtype: jnp.dtype = jnp.float32
    moe_experts: int = 0         # >0: pp×ep — each stage block's FFN is a
                                 # routed MoE (models/moe.py); the engine
                                 # reads this field to wire the router
                                 # aux-loss plumbing (engines/pipeline.py)
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    partition_experts: bool = False

    @nn.compact
    def __call__(self, x):
        lq = x.shape[1]
        if self.seq_axis and self.attention_impl == "dense":
            raise ValueError(
                "seq_axis set but attention_impl is 'dense' — dense "
                "attention on a seq-sharded carry attends within local "
                "blocks only; use ring/ring_flash/ulysses")
        pos = None
        if self.rope:
            offset = (coll.axis_index(self.seq_axis) * lq if self.seq_axis
                      else 0)
            pos = offset + jnp.arange(lq)[None, :]
        for _ in range(self.layers_per_stage):
            x = GPTBlock(self.hidden, self.heads, self.ffn,
                         dropout_rate=0.0,
                         attention_impl=self.attention_impl,
                         seq_axis=self.seq_axis or "seq",
                         partition_model=self.partition_model,
                         rope=self.rope, kv_heads=self.kv_heads,
                         dtype=self.dtype,
                         moe_experts=self.moe_experts,
                         moe_top_k=self.moe_top_k,
                         moe_capacity_factor=self.moe_capacity_factor,
                         partition_experts=self.partition_experts)(x, pos=pos)
        return x


class GPTPipeHead(nn.Module):
    """Output stage: final LN → untied LM head (see module comment)."""

    vocab_size: int = 256
    hidden: int = 128
    partition_model: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = nn.LayerNorm(dtype=self.dtype)(x)
        logits = nn.Dense(
            self.vocab_size, dtype=self.dtype,
            kernel_init=_part(nn.initializers.lecun_normal(),
                              (None, meshlib.MODEL_AXIS),
                              self.partition_model))(x)
        return logits.astype(jnp.float32)


def gpt_pipeline_stages(
    vocab_size: int = 256,
    hidden: int = 128,
    heads: int = 4,
    ffn: int = 512,
    max_len: int = 512,
    layers_per_stage: int = 1,
    partition_model: bool = False,
    positional: str = "learned",
    kv_heads: int | None = None,
    attention_impl: str = "dense",
    seq_axis: str | None = None,
    dtype: jnp.dtype = jnp.float32,
    num_classes: int | None = None,  # alias for vocab_size (harness passes it)
    moe_experts: int = 0,
    moe_top_k: int = 1,
    moe_capacity_factor: float = 1.25,
    partition_experts: bool = False,
):
    """(embed, block, head) for ``PipelineEngine(stages=...)``: a GPT decoder
    of depth ``pipe_axis_size × layers_per_stage``.  ``partition_model=True``
    adds Megatron TP annotations for pp×tp; ``positional='rope'`` drops the
    position table and rotates q/k inside each block;
    ``attention_impl='ring'`` (etc.) + ``seq_axis='seq'`` makes the stages
    sequence-parallel for pp×sp (the carry rides the pipe ring as a
    seq-sharded token block).  ``moe_experts > 0`` +
    ``partition_experts=True`` swaps each block's FFN for a routed MoE
    sharded over an 'expert' mesh axis (pp×ep, engines/pipeline.py)."""
    if num_classes is not None:
        vocab_size = num_classes
    if positional not in ("learned", "rope"):
        raise ValueError(
            f"unknown positional '{positional}'; learned | rope")
    rope = positional == "rope"
    return (
        GPTPipeEmbed(vocab_size=vocab_size, hidden=hidden, max_len=max_len,
                     partition_model=partition_model, rope=rope,
                     seq_axis=seq_axis, dtype=dtype),
        GPTPipeBlock(hidden=hidden, heads=heads, ffn=ffn,
                     layers_per_stage=layers_per_stage,
                     partition_model=partition_model, rope=rope,
                     kv_heads=kv_heads, attention_impl=attention_impl,
                     seq_axis=seq_axis, dtype=dtype,
                     moe_experts=moe_experts, moe_top_k=moe_top_k,
                     moe_capacity_factor=moe_capacity_factor,
                     partition_experts=partition_experts),
        GPTPipeHead(vocab_size=vocab_size, hidden=hidden,
                    partition_model=partition_model, dtype=dtype),
    )
