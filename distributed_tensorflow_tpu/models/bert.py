"""BERT-tiny sequence classifier — BASELINE.md's stretch config.

The reference has no attention or sequence models anywhere (SURVEY.md §2.2:
its only model is an MLP on 28×28, reference initializer.py:14-19);
BASELINE.md adds "BERT-tiny GLUE fine-tune" as a stretch benchmark.
Standard BERT-tiny shape: 2 layers, hidden 128, 2 heads, FFN 512.

Attention is pluggable (``attention_impl``):
  'dense'      — ordinary full attention; any mesh, no seq sharding.
  'flash'      — Pallas flash-attention kernel (ops.flash_attention): exact
                 same math as 'dense' but blockwise in VMEM — O(L) memory,
                 the TPU-native choice for long single-device sequences.
  'ring'       — ring attention over the ``seq`` mesh axis; the model must
                 run inside `jax.shard_map` with the token dim sharded over
                 'seq' (see engines.seq_parallel).  K/V rotate via ppermute.
  'ring_flash' — ring schedule with the Pallas flash kernel as the local
                 block math (parallel.ring_attention.ring_flash_attention):
                 long-context memory scaling with the kernel's VMEM-resident
                 score tiles.  Same contract as 'ring'.
  'ulysses'    — all-to-all head-parallel attention over 'seq'; same
                 contract, plus num_heads % seq_axis_size == 0.
  'ulysses_flash' — Ulysses reshard with the Pallas flash kernel as the
                 local math (each device holds the FULL sequence for H/n
                 heads after the all-to-all — exactly the single-device
                 flash case).  Same contract as 'ulysses'.

Input is int32 token ids (B, L_local); 0 is the padding id and is masked out
of attention.  The classification head reads the [CLS] position (global
index 0); under sequence parallelism only seq-device 0 holds it, so the head
uses a broadcast from that device.

``partition_model=True`` adds Megatron-style ``with_partitioning``
annotations over the ``model`` mesh axis for GSPMD tensor parallelism
(engines/tensor_parallel.py): QKV projections column-parallel (heads
sharded), attention output row-parallel, FFN split column→row, token
embedding vocab-sharded.  The activation between each col/row pair stays
model-sharded and XLA emits exactly one all-reduce per pair — no reference
counterpart (the reference replicates whole models, reference client.py:72).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from distributed_tensorflow_tpu.parallel import collectives as coll
from distributed_tensorflow_tpu.parallel import mesh as meshlib
from distributed_tensorflow_tpu.parallel.ring_attention import (
    dense_attention, ring_attention, ring_flash_attention,
    ulysses_attention, ulysses_flash_attention)


def _part(init, spec, enabled: bool):
    """Megatron annotation, applied only when the model is TP-partitioned
    (unannotated modules keep plain initializers so every non-GSPMD engine
    sees ordinary unboxed params)."""
    return nn.with_partitioning(init, spec) if enabled else init


class SelfAttention(nn.Module):
    hidden: int = 128
    heads: int = 2
    attention_impl: str = "dense"
    seq_axis: str = "seq"
    dropout_rate: float = 0.0   # attention-probability dropout (dense only:
                                # blockwise ring/ulysses skip it, as flash-
                                # style attention implementations do)
    partition_model: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, pad_mask, train: bool = False):
        head_dim = self.hidden // self.heads
        tp = self.partition_model
        # column-parallel QKV: kernel (hidden, heads*head_dim) with the packed
        # output dim sharded — when tp divides heads, the head reshape leaves
        # each model-device a contiguous slice of heads; otherwise GSPMD
        # reshards around the reshape (correct, but cross-head tp stops
        # paying off).  Plain Dense, not DenseGeneral: flax re-traces
        # DenseGeneral's boxed pre-reshape kernel at apply time, which breaks
        # under partial-manual shard_map meshes.
        def proj(name):
            h = nn.Dense(
                self.heads * head_dim, dtype=self.dtype, name=name,
                kernel_init=_part(nn.initializers.lecun_normal(),
                                  (None, meshlib.MODEL_AXIS), tp),
                bias_init=_part(nn.initializers.zeros_init(),
                                (meshlib.MODEL_AXIS,), tp))(x)
            return h.reshape(h.shape[:-1] + (self.heads, head_dim))

        q, k, v = proj("query"), proj("key"), proj("value")
        if self.attention_impl == "ring":
            out = ring_attention(q, k, v, axis=self.seq_axis, kv_mask=pad_mask)
        elif self.attention_impl == "ring_flash":
            out = ring_flash_attention(q, k, v, axis=self.seq_axis,
                                       kv_mask=pad_mask)
        elif self.attention_impl == "ulysses":
            out = ulysses_attention(q, k, v, axis=self.seq_axis, kv_mask=pad_mask)
        elif self.attention_impl == "ulysses_flash":
            out = ulysses_flash_attention(q, k, v, axis=self.seq_axis,
                                          kv_mask=pad_mask)
        elif self.attention_impl == "flash":
            from distributed_tensorflow_tpu.ops import flash_attention
            out = flash_attention(q, k, v, kv_mask=pad_mask)
        else:
            prob_fn = None
            if self.dropout_rate > 0.0:
                drop = nn.Dropout(self.dropout_rate, deterministic=not train)
                prob_fn = lambda p: drop(p)  # noqa: E731
            out = dense_attention(q, k, v, kv_mask=pad_mask, prob_fn=prob_fn)
        # row-parallel output: contraction over the packed (sharded) head dim
        # — XLA inserts the single all-reduce of the pair here
        out = out.reshape(out.shape[:-2] + (self.heads * head_dim,))
        return nn.Dense(
            self.hidden, dtype=self.dtype, name="out",
            kernel_init=_part(nn.initializers.lecun_normal(),
                              (meshlib.MODEL_AXIS, None), tp))(out)


class TransformerLayer(nn.Module):
    """Post-LN encoder layer.  ``moe_experts > 0`` swaps the dense FFN for
    a routed MoE layer over the layer's tokens (models/moe.py MoELayer,
    same contract as models/gpt.py GPTBlock: router diagnostics sow into
    ``intermediates``; under seq parallelism each seq device routes its
    own token block to the 'expert'-sharded experts)."""

    hidden: int = 128
    heads: int = 2
    ffn: int = 512
    dropout_rate: float = 0.1
    attention_impl: str = "dense"
    seq_axis: str = "seq"
    partition_model: bool = False
    dtype: jnp.dtype = jnp.float32
    moe_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    partition_experts: bool = False

    @nn.compact
    def __call__(self, x, pad_mask, train: bool = False):
        tp = self.partition_model
        y = SelfAttention(self.hidden, self.heads, self.attention_impl,
                          self.seq_axis, self.dropout_rate, tp,
                          self.dtype)(x, pad_mask, train)
        x = nn.LayerNorm(dtype=self.dtype)(x + y)
        if self.moe_experts > 0:
            from distributed_tensorflow_tpu.models.moe import moe_ffn

            y = moe_ffn(x, hidden=self.ffn, moe_experts=self.moe_experts,
                        moe_top_k=self.moe_top_k,
                        moe_capacity_factor=self.moe_capacity_factor,
                        partition_experts=self.partition_experts,
                        partition_model=tp, dtype=self.dtype)
        else:
            # Megatron FFN: column-parallel expand, row-parallel contract —
            # the (B, L, ffn) activation never leaves its model shard
            y = nn.Dense(
                self.ffn, dtype=self.dtype,
                kernel_init=_part(nn.initializers.lecun_normal(),
                                  (None, meshlib.MODEL_AXIS), tp),
                bias_init=_part(nn.initializers.zeros_init(),
                                (meshlib.MODEL_AXIS,), tp))(x)
            y = nn.gelu(y)
            y = nn.Dense(
                self.hidden, dtype=self.dtype,
                kernel_init=_part(nn.initializers.lecun_normal(),
                                  (meshlib.MODEL_AXIS, None), tp))(y)
        y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        return nn.LayerNorm(dtype=self.dtype)(x + y)


class BertEmbeddings(nn.Module):
    """Token + position embeddings → LayerNorm.  Shared by the monolithic
    classifier and the pipeline embed stage; callers supply the position ids
    (seq-parallel blocks pass offset positions) and own the max_len check."""

    vocab_size: int = 8192
    hidden: int = 128
    max_len: int = 512
    partition_model: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, token_ids, pos):
        # vocab-sharded token embedding (Megatron): the vocab dim is the one
        # that grows; GSPMD renders the sharded gather as masked-lookup+psum
        x = nn.Embed(
            self.vocab_size, self.hidden, dtype=self.dtype,
            embedding_init=_part(nn.linear.default_embed_init,
                                 (meshlib.MODEL_AXIS, None),
                                 self.partition_model))(token_ids)
        x = x + nn.Embed(self.max_len, self.hidden, dtype=self.dtype)(pos)
        return nn.LayerNorm(dtype=self.dtype)(x)


class BertPooler(nn.Module):
    """[CLS] readout: tanh pooler → classifier logits (f32 for the softmax).
    Shared by the monolithic classifier and the pipeline head."""

    num_classes: int = 2
    hidden: int = 128
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, cls):
        cls = nn.tanh(nn.Dense(self.hidden, dtype=self.dtype)(cls))
        logits = nn.Dense(self.num_classes, dtype=self.dtype)(cls)
        return logits.astype(jnp.float32)


class BertTinyClassifier(nn.Module):
    num_classes: int = 2
    vocab_size: int = 8192
    hidden: int = 128
    layers: int = 2
    heads: int = 2
    ffn: int = 512
    max_len: int = 512
    dropout_rate: float = 0.1
    attention_impl: str = "dense"
    seq_axis: str = "seq"
    partition_model: bool = False
    remat: bool = False          # activation checkpointing per encoder
                                 # layer (see models/gpt.py GPTLM.remat)
    moe_experts: int = 0         # >0: every layer's FFN is a routed MoE
                                 # (models/moe.py; see GPTLM.moe_experts)
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    partition_experts: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, token_ids, train: bool = False):
        seq_parallel = self.attention_impl in ("ring", "ring_flash",
                                               "ulysses", "ulysses_flash")
        pad_mask = (token_ids > 0).astype(self.dtype)
        lq = token_ids.shape[1]
        # nn.Embed clamps out-of-range gathers silently — fail loudly instead
        global_len = lq * (coll.axis_size(self.seq_axis) if seq_parallel else 1)
        if global_len > self.max_len:
            raise ValueError(
                f"sequence length {global_len} exceeds max_len={self.max_len}; "
                f"raise max_len or shorten the input")
        if seq_parallel:
            # local block's global positions: block index × local length
            offset = coll.axis_index(self.seq_axis) * lq
            pos = offset + jnp.arange(lq)[None, :]
        else:
            pos = jnp.arange(lq)[None, :]
        x = BertEmbeddings(self.vocab_size, self.hidden, self.max_len,
                           self.partition_model, self.dtype)(token_ids, pos)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        # remat: train (arg 3) is a static python bool; x/pad_mask trace.
        # Explicit name pins the module path to the unwrapped auto-name —
        # nn.remat renames the class, and flax derives param paths + init
        # RNG from the path, so without the pin remat=True would draw
        # different params under different tree paths (see models/gpt.py).
        if self.remat and self.moe_experts:
            raise ValueError(
                "remat + MoE layers is unsupported: the router's sown "
                "intermediates would be re-sown during backward recompute "
                "(see models/gpt.py)")
        layer_cls = (nn.remat(TransformerLayer, static_argnums=(3,))
                     if self.remat else TransformerLayer)
        for i in range(self.layers):
            x = layer_cls(self.hidden, self.heads, self.ffn,
                          self.dropout_rate, self.attention_impl,
                          self.seq_axis, self.partition_model,
                          self.dtype, self.moe_experts, self.moe_top_k,
                          self.moe_capacity_factor, self.partition_experts,
                          name=f"TransformerLayer_{i}")(x, pad_mask, train)
        cls = x[:, 0]  # [CLS]: global position 0
        if seq_parallel:
            # only seq-device 0 holds the real [CLS]; replicate it so the
            # head computes identically on every seq device
            cls = coll.broadcast_from(cls, self.seq_axis, src=0)
        return BertPooler(self.num_classes, self.hidden, self.dtype)(cls)


# --------------------------------------------------------------------------
# GPipe stage modules (engines/pipeline.py `stages=` plug-in): the encoder
# splits into embed → S identical TransformerLayer stages → [CLS] head.  The
# pipeline carry is (hidden_states, pad_mask) — the mask must travel with the
# activations because later stages never see the token ids.  Deterministic by
# construction (no dropout): the GPipe schedule re-applies embed/head every
# tick, so rng-consuming ops would draw inconsistent masks across ticks.
# --------------------------------------------------------------------------


class BertPipeEmbed(nn.Module):
    """Input stage: token + position embeddings → (hidden, pad_mask) carry."""

    vocab_size: int = 8192
    hidden: int = 128
    max_len: int = 512
    partition_model: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, token_ids):
        pad_mask = (token_ids > 0).astype(self.dtype)
        if token_ids.shape[1] > self.max_len:
            raise ValueError(
                f"sequence length {token_ids.shape[1]} exceeds "
                f"max_len={self.max_len}")
        pos = jnp.arange(token_ids.shape[1])[None, :]
        x = BertEmbeddings(self.vocab_size, self.hidden, self.max_len,
                           self.partition_model, dtype=self.dtype)(
                               token_ids, pos)
        return x, pad_mask


class BertPipeBlock(nn.Module):
    """One pipeline stage: ``layers_per_stage`` transformer layers
    (hidden-preserving, so stages stack and shard P('pipe')).

    ``partition_model=True`` adds the Megatron annotations for pp×tp: the
    stacked stage params then shard ('pipe', …Megatron spec…) and GSPMD
    owns the in-stage model-axis collectives (engines/pipeline.py)."""

    hidden: int = 128
    heads: int = 2
    ffn: int = 512
    layers_per_stage: int = 1
    partition_model: bool = False
    dtype: jnp.dtype = jnp.float32
    moe_experts: int = 0         # >0: pp×ep — routed MoE FFN per layer
                                 # (models/moe.py; engines/pipeline.py reads
                                 # this field for the aux-loss plumbing)
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    partition_experts: bool = False

    @nn.compact
    def __call__(self, carry):
        x, pad_mask = carry
        for _ in range(self.layers_per_stage):
            x = TransformerLayer(self.hidden, self.heads, self.ffn,
                                 dropout_rate=0.0, attention_impl="dense",
                                 partition_model=self.partition_model,
                                 dtype=self.dtype,
                                 moe_experts=self.moe_experts,
                                 moe_top_k=self.moe_top_k,
                                 moe_capacity_factor=self.moe_capacity_factor,
                                 partition_experts=self.partition_experts)(
                                     x, pad_mask)
        return x, pad_mask


class BertPipeHead(nn.Module):
    """Output stage: the shared [CLS] pooler over the carry's activations."""

    num_classes: int = 2
    hidden: int = 128
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, carry):
        x, _ = carry
        return BertPooler(self.num_classes, self.hidden, self.dtype)(x[:, 0])


def bert_pipeline_stages(
    num_classes: int = 2,
    vocab_size: int = 8192,
    hidden: int = 128,
    heads: int = 2,
    ffn: int = 512,
    max_len: int = 512,
    layers_per_stage: int = 1,
    partition_model: bool = False,
    dtype: jnp.dtype = jnp.float32,
    moe_experts: int = 0,
    moe_top_k: int = 1,
    moe_capacity_factor: float = 1.25,
    partition_experts: bool = False,
):
    """(embed, block, head) for ``PipelineEngine(stages=...)``: a BERT
    encoder of depth ``pipe_axis_size × layers_per_stage``.
    ``partition_model=True`` adds Megatron TP annotations for pp×tp;
    ``moe_experts > 0`` + ``partition_experts=True`` makes each layer's FFN
    a routed MoE sharded over an 'expert' mesh axis (pp×ep,
    engines/pipeline.py)."""
    return (
        BertPipeEmbed(vocab_size=vocab_size, hidden=hidden, max_len=max_len,
                      partition_model=partition_model, dtype=dtype),
        BertPipeBlock(hidden=hidden, heads=heads, ffn=ffn,
                      layers_per_stage=layers_per_stage,
                      partition_model=partition_model, dtype=dtype,
                      moe_experts=moe_experts, moe_top_k=moe_top_k,
                      moe_capacity_factor=moe_capacity_factor,
                      partition_experts=partition_experts),
        BertPipeHead(num_classes=num_classes, hidden=hidden, dtype=dtype),
    )
