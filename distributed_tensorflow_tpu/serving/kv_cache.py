"""Slot-based sharded KV cache: the device half of the serving engine.

Static batching idles the chip on every finished sequence — a batch of
requests decodes at the pace of its longest member, and admitting a new
request means restarting ``generate`` from scratch.  Continuous batching
(Orca/vLLM-style in-flight batching) fixes that by making the *batch slot*,
not the batch, the unit of scheduling: the KV cache is a fixed table of
``slots`` independent sequences, each with its own length, and ONE compiled
single-token decode step advances every active slot regardless of age.
Admission and eviction are per-slot edits between decode iterations — the
decode program never recompiles.

Device-side contract (everything else lives in serving/scheduler.py):

* the cache is a pytree with the slot dim first on every leaf, and THREE
  kinds of leaf, which the served model tells apart (its ``slot_rings``
  names the second kind and its ``slot_state`` the third; a model with
  neither attribute keeps the first kind only):

  - per-position ROWS ``(slots, max_len, ...)``: per-head keys and values
    ``(slots, max_len, kv_heads, head_dim)`` for models/gpt.py, for the
    attention layers of models/hybrid_ssm.py and models/jamba.py and for
    the full-attention layers of models/window_moe.py, a latent and one
    rotated key head
    ``(slots, max_len, rank)`` for models/mla_moe.py.  Validity is
    LENGTH-DRIVEN: a row at or past the slot's length is never attended,
    so a stale row, a pad row of a prefill bucket and the row a free or
    excluded slot writes at its own length are all invisible, and the
    next real write lands over them;
  - per-position RINGS ``(slots, ring, ...)`` with ``ring`` rows a slot
    whatever ``max_len`` is: the keys and values of a layer whose queries
    see the last ``ring`` positions only (the window layers of
    models/window_moe.py; ``slot_rings`` gives each leaf's ``ring``).
    Position ``p`` lives in row ``p mod ring``.  Validity is length-driven
    by ANOTHER rule: rows ``0 .. min(length, ring) - 1`` are valid and
    hold the slot's last ``min(length, ring)`` positions, in an order
    that does not matter (keys are stored with their position term
    applied).  A stale row past the length is invisible as above, and a
    short request that takes the slot of a long one finds its rows stale
    beyond its own length only.  But a row BELOW the length is not
    protected by the length: a PREFILL must put exactly the positions
    ``max(0, n - ring) .. n - 1`` of an ``n``-token prompt there and
    nothing for its bucket's pads (pad position ``p`` would land on the
    row of the real position ``p - ring``), and the STEP writes row
    ``length mod ring``, the row of the one position that has just left
    every later query's window; a free or excluded slot's write there is
    covered by its own next real write before anything reads it.  What
    moves validity backwards or copies a slot by rows of ``max_len``
    cannot hold over a ring (the row a rewind would uncover is
    overwritten): ``rewind``, ``commit_block`` / ``verify_block``, the
    prefix pool, chunk resume, the paged layout, multi-step dispatch and
    KV handoff raise ``NotImplementedError`` by name for a model with
    rings (int8 storage and a tensor-parallel table are refused by the
    model's own ``slot_decode_clone``);
  - per-slot STATE ``(slots, ...)`` with no position axis, of whatever
    shape the model gives it: the recurrent state ``(slots, heads,
    head_dim, state)`` (models/hybrid_ssm.py) or ``(slots, d_inner,
    d_state)`` (models/jamba.py) and the convolution tail ``(slots, taps -
    1, width)`` of a state-space layer.  Nothing about it is
    length-driven: the whole leaf is valid at every moment, and a second
    write advances it twice.
    So a PREFILL starts the slot's state from zero whatever the last
    occupant left and makes the bucket's pad rows inert (the state written
    is the state after ``prompt_len`` tokens, the tail its last real
    rows), the STEP is handed ``active`` and keeps the state of a slot
    that is not active bit for bit, and validity cannot be moved by
    bookkeeping: ``rewind``, ``commit_block`` / ``verify_block``, the
    prefix pool, chunk resume, the paged layout, int8 storage, multi-step
    dispatch, KV handoff and a tensor-parallel table raise
    ``NotImplementedError`` by name for a model with state (no state
    snapshot is built).

  Deliberately no scalar cursors, so every leaf shards the slot dim over
  the mesh's ``data`` axis and, for tensor-parallel models, the kv-head
  dim over ``model`` (parallel/mesh.py ``kv_slot_sharding``);
* ``advance`` is the one jitted decode step: (tokens, lengths, active)
  vectors in, next tokens out, cache donated through;
* ``insert`` is a jitted prefill against only that slot's cache slice
  (batch 1), written back once — compiled once per padded length bucket
  (powers of two), so steady-state admission never triggers XLA.  It is
  ONE call of the served module over the whole padded prompt from
  position 0 (``prompt_len`` marks it: the block attends within itself,
  writes the slot's rows in one piece and returns logits for the last
  real position only), for models/gpt.py, models/mla_moe.py and
  models/hybrid_ssm.py alike: a prompt token then costs its FLOPs, where
  a scan of the one-token step read every weight (and every expert) once
  a token;
* ``begin_insert``/``prefill_chunk`` split that admission into fixed
  token-budget chunks (Sarathi-Serve, arXiv:2403.02310): each chunk feeds
  its tokens through the SAME per-token decode math inside a ``lax.scan``
  and resumes at the slot's fill position (the chunk program takes a
  traced ``start``, so ONE compile per power-of-two chunk-length bucket
  serves every resume point), and the scheduler interleaves at most one
  chunk per decode iteration — live slots keep emitting tokens while a
  long prompt fills;
* the optional **prefix pool** (vLLM PagedAttention's block-granular KV
  reuse, arXiv:2309.06180) caches block-aligned prompt-prefix KV keyed by
  the exact token bytes of the prefix: on admission the longest cached
  prefix is copied into the slot and prefill starts at the first uncached
  block, with hit/miss/evict accounting and bounded LRU eviction.

Greedy slot decode is token-identical to the sequential ``generate``
sampler per request (tests/test_serving.py): the chunk scan at position t
and decode-at-cursor-t run the same dense cache attention with the same
length-driven validity mask, and the block prefill computes the same
causal attention over the prompt in one piece.  Chunked prefill is
bitwise-identical across chunk budgets (each token's forward depends only
on cache positions below its own, all written by earlier chunks), and a
prefix-cache hit is bitwise-identical to recomputation (the pooled KV is
a byte copy of what the cold chunk scan would write).  Against the block
prefill of ``insert`` the chunk scan agrees in TOKENS; the table rows the
two write agree to float32 rounding, not bitwise (one matrix product over
L rows where the scan made L products over one).

Round 14 adds the two raw-decode-speed levers (ROADMAP item 3):
``verify_block``/``commit_block``/``rewind`` — the speculative-decode
device step (one batched program scores a (slots, k+1) token block;
acceptance/rollback is length bookkeeping alone, the same
validity-is-length-driven argument as chunk resume) — and
``kv_dtype='int8'`` — K/V stored int8 with one f32 max-abs scale per
written vector, the scale leaves riding the same sharded cache pytree
(``kv_bytes_per_slot`` is the capacity number; token parity vs the bf16
oracle is tolerance-based, the one serving feature with that caveat).
"""

from __future__ import annotations

import hashlib
import inspect
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import flax.linen as nn

from distributed_tensorflow_tpu.observability.trace import recorder
from distributed_tensorflow_tpu.parallel import mesh as meshlib


def _bucket(n: int, floor: int, cap: int) -> int:
    """Smallest power-of-two ≥ max(n, floor), capped at ``cap`` — the
    padded prompt length, so prefill compiles once per bucket instead of
    once per prompt length."""
    b = max(int(floor), 1)
    while b < n:
        b *= 2
    return min(b, cap)


def _tree_bytes(tree) -> int:
    """Bytes of a tree's leaves (arrays or ``jax.ShapeDtypeStruct``)."""
    return sum(int(t.size) * jnp.dtype(t.dtype).itemsize
               for t in jax.tree.leaves(tree))


def _check_swap(served, params) -> None:
    """A swapped-in tree has to be a cache hit of every compiled program:
    the served tree's structure, shapes and dtypes."""
    if (jax.tree_util.tree_structure(served)
            != jax.tree_util.tree_structure(params)):
        raise ValueError(
            "swap_params needs the same param tree structure as the "
            "served checkpoint (same model config) — a different "
            "architecture cannot hot-swap into live slots")
    mismatch = [
        f"{jax.tree_util.keystr(path)}: {a.shape}/{a.dtype} vs "
        f"{b.shape}/{b.dtype}"
        for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(served)[0],
            jax.tree.leaves(params))
        if a.shape != b.shape or a.dtype != b.dtype]
    if mismatch:
        raise ValueError(
            f"swap_params shape/dtype mismatch (a swap must be a "
            f"compiled-program cache hit): {mismatch[:3]}")


class SlotOverflow(RuntimeError):
    """An active slot was asked to write past its ``max_len`` capacity.

    The scheduler guards admission (prompt + max_new_tokens ≤ max_len), so
    reaching this means a bookkeeping bug, not a user error — the serving
    twin of the training path's sticky cache-overflow flag (models/gpt.py
    ADVICE r3: never silently clamp)."""


class BlockPoolExhausted(RuntimeError):
    """The paged KV block pool has no free physical block for a required
    write.  Admission-level pool pressure is the SCHEDULER's problem
    (``PagedSlotKVCache.can_admit`` + block budgets defer admissions);
    reaching this mid-flight means the block accounting is broken — the
    paged twin of ``SlotOverflow``, not an overload signal."""


class SlotKVCache:
    """Fixed slot table + compiled prefill/decode programs for one model
    with a slot-decode mode (``models/gpt.GPTLM``,
    ``models/mla_moe.LatentMoELM``, ``models/hybrid_ssm.HybridSSMLM``,
    ``models/window_moe.WindowMoELM``).

    ``model`` is the TRAINING-mode module (any attention impl); its
    ``slot_decode_clone`` gives the module served from — for ``GPTLM``
    exactly like ``generate`` clones into cursor-decode mode: dense cache
    attention, dropout off, Megatron TP layout kept when ``mesh`` has a
    'model' axis and the model was partitioned.  The table's leaves come
    from that module's own abstract init.  What is built from a resumable
    one-token step over per-head K/V rows — the paged layout, chunk
    resume, the prefix pool, int8 storage, multi-step dispatch,
    speculative verify, KV handoff — raises ``NotImplementedError`` by
    name for a model without one (``resumable_step`` on the model class),
    and so does what moves validity by bookkeeping for a model that keeps
    per-slot state (``slot_state`` on the model class) or rings
    (``slot_rings``).
    ``params`` may be a TP engine's committed TrainState params or
    host/single-device params (replicated over the mesh).  The table
    HOLDS each leaf in the dtype in which the model's call first uses it
    (``_place_params``; the model's ``step_param_dtype`` is the rule): a
    float32 ``GPTLM`` checkpoint served at ``dtype=bfloat16`` is held as
    bfloat16 kernels, biases and embeddings beside float32 ``LayerNorm``
    leaves, converted once when the table takes it, and ``self.params``
    is then not the caller's tree (which is not donated: it is freed
    when the caller lets go of it).  A tree that is in those dtypes
    already — a model that declares no rule, a float32 model, another
    table's ``params`` — is used in place, leaf for leaf the caller's
    arrays.  A tensor-parallel engine's committed params, once "used in
    place", so get a bfloat16 twin with the same sharding where the model
    computes in bfloat16.  ``param_bytes`` is the held tree's size.

    Host-side bookkeeping (`lengths`, `active`, `tokens`) lives on numpy:
    the scheduler owns admission/eviction and the decode step receives the
    vectors as arguments, so slot edits never touch device state except
    through the two compiled programs.
    """

    def __new__(cls, *args, kv_layout: str = "monolithic", **kwargs):
        # --serve-kv-layout dispatch: constructing a SlotKVCache with
        # kv_layout="paged" yields the paged subclass, so every call site
        # (harness, fleet's build_replica_kvs **kv_kwargs
        # pass-through) selects the layout with one kwarg and no factory
        if cls is SlotKVCache and kv_layout == "paged":
            return super().__new__(PagedSlotKVCache)
        return super().__new__(cls)

    def __init__(self, model: nn.Module, params, slots: int, *,
                 mesh=None, greedy: bool = True, temperature: float = 1.0,
                 prefill_bucket: int = 8, rng=None, kv_dtype=None,
                 prefix_cache_blocks: int = 0, prefix_block: int = 16,
                 kv_layout: str = "monolithic", paged_blocks: int = 0,
                 paged_block: int = 0, paged_fused: bool = True,
                 ledger=None):
        if kv_layout not in ("monolithic", "paged"):
            raise ValueError(
                f"kv_layout must be 'monolithic' or 'paged', "
                f"got {kv_layout!r}")
        if paged_blocks or paged_block:
            raise ValueError(
                "paged_blocks/paged_block only apply to "
                "kv_layout='paged'")
        self.kv_layout = "monolithic"
        # --timeline's XLA memory/compile ledger: when attached, every
        # compiled program routes through ledger.jit (same program, AOT-
        # observed); None keeps the literal jax.jit path byte-identical
        self._ledger = ledger
        if slots < 1:
            raise ValueError(f"slots must be positive, got {slots}")
        if prefix_cache_blocks < 0:
            raise ValueError(f"prefix_cache_blocks must be >= 0, got "
                             f"{prefix_cache_blocks}")
        if prefix_block < 1:
            raise ValueError(f"prefix_block must be positive, got "
                             f"{prefix_block}")
        self.slots = int(slots)
        self.max_len = int(model.max_len)
        self.greedy = bool(greedy)
        self.temperature = float(temperature)
        self.prefill_bucket = int(prefill_bucket)
        self.mesh = mesh
        # --serve-kv-dtype int8: the model stores K/V as int8 with one f32
        # max-abs scale per written vector (models/gpt.py kv_quant) — the
        # scale leaves ride the SAME cache pytree, so the slot dim shards
        # over 'data' exactly like the payload.  Quantize on write,
        # dequantize on the attention read; token parity vs the bf16
        # oracle is tolerance-based (greedy-token agreement), not bitwise.
        self.quantized = False
        if kv_dtype is not None:
            kv_dtype = jnp.dtype(kv_dtype)
            self.quantized = kv_dtype == jnp.dtype(jnp.int8)
        keep_tp = (mesh is not None
                   and getattr(model, "partition_model", False)
                   and meshlib.MODEL_AXIS in mesh.axis_names)
        self.resumable_step = model.resumable_step
        # the leaves that are per-slot state and not per-position rows,
        # by name, and whether ``kv_dtype`` may narrow each; the leaves
        # that are rings, by name, and their rows (module docstring: the
        # three kinds)
        self.state_leaves = dict(getattr(model, "slot_state", {}))
        self.ring_leaves = dict(getattr(model, "slot_rings", {}))
        self.served_model = type(model).__name__    # named in a refusal
        # what ``insert`` runs (the ``prefill`` span's ``form``): one call
        # over the padded prompt, or the chunk scan the prefix pool needs
        self.prefill_form = "scan" if prefix_cache_blocks else "batched"
        if prefix_cache_blocks:
            self._scan_model_only("the prefix pool")
        self.dm = model.slot_decode_clone(partition_model=keep_tp,
                                          kv_quant=self.quantized)
        self._rng = rng if rng is not None else jax.random.key(0)

        # zero slot cache from an abstract init — zeros-from-shape IS the
        # init value (same argument as models/gpt.py `generate`)
        dummy = jnp.zeros((self.slots, 1), jnp.int32)
        shapes = jax.eval_shape(
            lambda: self.dm.init(jax.random.key(0), dummy, train=False,
                                 positions=dummy))["cache"]
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        if kv_dtype is not None and not self.quantized:
            # --serve-kv-dtype bfloat16: store the K/V table narrower than
            # the model computes (bf16 halves KV memory → double the slots
            # per chip).  The model's slot writes cast to the
            # table's dtype (models/gpt.py) and the attention read
            # promotes back, so the decode program stays the one compiled
            # step.  (int8 needs no cast here — the kv_quant model
            # already initializes int8 payload + f32 scale leaves.)
            # A state leaf the model pins (a recurrent state: an error in
            # it is carried through every later token) stays as made.
            cache = jax.tree_util.tree_map_with_path(
                lambda path, t: t.astype(kv_dtype)
                if jnp.issubdtype(t.dtype, jnp.floating)
                and self.state_leaves.get(path[-1].key, True) else t, cache)
        # the table's actual storage dtype, surfaced in the serve report
        # section (for int8 the first FLOAT leaf is a scale, so the name
        # is pinned explicitly; otherwise it is the rows' dtype)
        self.kv_dtype = "int8" if self.quantized else next(
            (str(leaf.dtype) for path, leaf in
             jax.tree_util.tree_leaves_with_path(cache)
             if jnp.issubdtype(leaf.dtype, jnp.floating)
             and path[-1].key not in self.state_leaves), "float32")

        self._vec_sharding = None
        self._blk_sharding = None
        if mesh is not None:
            dp = mesh.shape.get(meshlib.DATA_AXIS, 1)
            if self.slots % dp:
                raise ValueError(
                    f"slots ({self.slots}) must divide by the mesh's data "
                    f"axis ({dp}): each data shard owns a contiguous slot "
                    f"block")
            cache = jax.tree.map(
                lambda t: jax.device_put(t, meshlib.kv_slot_sharding(
                    mesh, t.ndim, shard_heads=keep_tp)), cache)
            self._vec_sharding = meshlib.kv_slot_sharding(mesh, 1)
            self._blk_sharding = meshlib.kv_slot_sharding(mesh, 2)
        self.cache = cache

        # host-side slot table.  ``reserved`` marks slots claimed by an
        # in-progress chunked admission (begin_insert): not free, but not
        # yet advanced by decode — lengths[] tracks the fill position.
        self.lengths = np.zeros(self.slots, np.int32)
        self.active = np.zeros(self.slots, np.bool_)
        self.reserved = np.zeros(self.slots, np.bool_)
        self.tokens = np.zeros(self.slots, np.int32)   # last token per slot
        self._pending: dict[int, dict] = {}            # slot → prefill state
        self._init_multi_state()
        self.params = self._place_params(params)    # under self.tracer

        # block-aligned prefix pool (LRU over exact prefix-byte keys);
        # entries are the slot-slice KV of one block, stored at the table's
        # dtype so a hit writes back bitwise what the cold prefill wrote
        self.prefix_cache_blocks = int(prefix_cache_blocks)
        self.prefix_block = int(prefix_block)
        self._prefix_pool: OrderedDict[bytes, object] = OrderedDict()
        self.prefix_stats = {"hits": 0, "misses": 0, "evictions": 0,
                             "tokens_reused": 0, "inserted_blocks": 0}

        # prompt tokens actually fed through a prefill program (cached
        # prefix blocks are skipped, pad tokens not counted) — the
        # scheduler reads deltas of this for the prefill/decode token
        # split and the VirtualClock interference model
        self.prefill_tokens_computed = 0
        # positions the prefill programs ran for them: each call's
        # bucket, pad tokens included (the ``prefill`` span's padded_len)
        self.prefill_tokens_padded = 0

        # host-observed seconds inside the compiled programs, per phase
        # (cumulative; the scheduler reads deltas per run) — the device
        # half of the per-request phase attribution: how much of a
        # window went to prefill programs vs decode steps
        self._phase_s = {"prefill_s": 0.0, "decode_s": 0.0}

        self._step = self._build_step()
        self._prefills: dict[int, object] = {}
        self._chunks: dict[int, object] = {}           # chunk-resume prefill
        self._verifies: dict[int, object] = {}         # speculative verify
        self._read_block = None                        # prefix-pool extract
        self._write_block = None                       # prefix-pool restore
        self._handoff_read = None                      # disagg KV handoff
        self._handoff_write = None

    def _init_multi_state(self) -> None:
        """Shared (monolithic + paged) init for the device-resident
        vector cache and the multi-step decode state.

        ``_dev_vecs`` is the value-keyed host→device cache behind
        ``_dev_cached``: slot vectors (tokens/lengths/mask) stay on
        device between iterations and re-upload only when the host VALUE
        changed — the explicit host-mirror sync point.  ``eos_tok`` /
        ``budget`` arm the fused program's in-device deactivation
        (-1 = no EOS, 0 = unlimited budget — the draft table's mode);
        ``halted`` mirrors slots the device stopped advancing that the
        scheduler has not yet evicted (occupancy ``active`` is separate);
        ``dispatch_count`` counts every compiled-program host call;
        ``tracer`` takes the table's own spans: ``program_build`` and, in
        a single-step round, ``step_dispatch`` and ``token_fetch`` (the
        batcher that drives this table hands it its own)."""
        self.tracer = recorder()
        # a model with routed experts: the decode step also returns the
        # round's routing load (advance -> last_routing)
        self.expert_layers = int(getattr(self.dm, "expert_layers", 0))
        self.last_routing: dict[str, float] | None = None
        self.expert_assignments = 0     # (token, expert) pairs, cumulative
        # a model whose block prefill runs ``ops/selective_scan``: in how
        # many layers, and the positions and prompt tokens that went
        # through the kernel (cumulative; ``counters``)
        self.scan_layers = int(getattr(self.dm, "selective_scan_layers", 0))
        self.ssm_scan_positions = 0
        self.ssm_scan_tokens = 0
        self.eos_tok = np.full(self.slots, -1, np.int32)
        self.budget = np.zeros(self.slots, np.int32)
        self.halted = np.zeros(self.slots, np.bool_)
        self.dispatch_count = 0
        self._dev_vecs: dict[str, tuple[np.ndarray, object]] = {}
        self._multis: dict[int, object] = {}    # k → fused decode program
        self._multi_state = None    # device carry after the last dispatch
        self._multi_snap = None     # host view at the last dispatch
        self._multi_pending: list[dict] = []    # in-flight rounds (FIFO)
        self._inflight = np.zeros(self.slots, np.int32)

    def _scan_model_only(self, feature: str) -> None:
        """What rests on a scan of the one-token step from any start
        position and on per-head K/V leaves is refused by name for a model
        that has no such step."""
        self._rows_only(feature)
        if not self.resumable_step:
            raise NotImplementedError(
                f"{feature} is not implemented for a model without a "
                f"resumable one-token step (models/mla_moe.py): the "
                f"monolithic table with insert/advance/evict is")

    def _rows_only(self, feature: str) -> None:
        """What moves a slot's validity by bookkeeping, or copies a slot
        by its rows, is refused by name for a model that keeps per-slot
        state (a recurrent state cannot be taken back or resumed from a
        position, and no snapshot of it is built) and for a model that
        keeps rings (the row a shorter length would uncover has been
        overwritten, and a ring has no rows of ``max_len`` to copy)."""
        if self.ring_leaves:
            raise NotImplementedError(
                f"{feature} is not implemented for a model that keeps "
                f"rings of its last positions beside its full-length rows "
                f"({self.served_model}: {sorted(self.ring_leaves)}): "
                f"the monolithic table with insert/advance/evict is")
        if self.state_leaves:
            raise NotImplementedError(
                f"{feature} is not implemented for a model that keeps "
                f"per-slot state beside its rows "
                f"({self.served_model}: {sorted(self.state_leaves)}): "
                f"the monolithic table with insert/advance/evict is")

    def _place_params(self, params, *, replacing=None):
        """What the table does with a tree it is given: the one door of
        ``__init__`` and ``swap_params``, under a ``params_place`` span
        (``narrowed``: leaves converted; ``bytes_given``, ``bytes_held``:
        the tree before and after).

        *Narrowing* (``_narrow_params``, the class docstring): each leaf
        in the dtype the model's call first uses it in; a leaf that needs
        nothing is the very same array, and the caller's tree is not
        donated.  *The check of a swap*: ``replacing`` is the served tree,
        which the narrowed tree has to match in structure, shapes and
        dtypes (``ValueError`` otherwise, and nothing is placed).
        *Placement*: with a mesh, a leaf committed to it is used in place
        and anything else replicates (the ``generate(mesh=...)`` rule)."""
        with self.tracer.span("params_place") as attrs:
            held = self._narrow_params(params)
            attrs["bytes_given"] = _tree_bytes(params)
            attrs["narrowed"] = sum(
                a is not b for a, b in zip(jax.tree.leaves(held),
                                           jax.tree.leaves(params)))
            if replacing is not None:
                _check_swap(replacing, held)
            if self.mesh is not None:
                mesh = self.mesh
                repl = NamedSharding(mesh, P())
                target = mesh.devices.tolist()

                def place(t):
                    sh = getattr(t, "sharding", None)
                    if isinstance(sh, NamedSharding) and (
                            sh.mesh is mesh
                            or sh.mesh.devices.tolist() == target):
                        return t
                    return jax.device_put(t, repl)

                held = jax.tree.map(place, held)
            self.param_bytes = attrs["bytes_held"] = _tree_bytes(held)
        return held

    def _narrow_params(self, params):
        """Each leaf in the dtype in which the served model's call first
        uses it.  A leaf that the model says its call converts before the
        first use (``step_param_dtype`` on the model: ``GPTLM``'s
        ``Dense`` and ``Embed`` leaves go to ``model.dtype``, its
        ``LayerNorm`` leaves do not), and that is wider than that dtype,
        is converted here once: the bits the call would make, which every
        program then reads at half the bytes and without a copy of its
        own.  Any other leaf is returned as the very same array: a model
        with no rule, a float32 model and a tree narrowed before get
        their tree back.  A ``jax.ShapeDtypeStruct`` is narrowed
        abstractly."""
        use_dtype = getattr(self.dm, "step_param_dtype", None)
        if use_dtype is None:
            return params

        def narrow(path, t):
            want = use_dtype(tuple(
                k.key for k in path
                if isinstance(k, jax.tree_util.DictKey)))
            if (want is None or not jnp.issubdtype(t.dtype, jnp.floating)
                    or jnp.dtype(t.dtype).itemsize
                    <= jnp.dtype(want).itemsize):
                return t
            if isinstance(t, jax.ShapeDtypeStruct):
                return jax.ShapeDtypeStruct(t.shape, want,
                                            sharding=t.sharding)
            return t.astype(want)

        return jax.tree_util.tree_map_with_path(narrow, params)

    def swap_params(self, params) -> None:
        """Zero-downtime weight hot-swap: replace the served params
        between compiled-program dispatches (serving/fleet.py drains a
        replica's in-flight slots first — KV written under the old params
        must never be decoded under the new ones).  The tree is taken as
        ``__init__`` takes one (a float32 checkpoint of a bfloat16 model
        is narrowed by the same rule, ``_place_params``) and must THEN
        match the served one's structure/shapes/dtypes, so every compiled
        program (decode step, prefill buckets, chunk buckets, verify
        widths) stays a cache hit — a swap never recompiles."""
        self.params = self._place_params(params, replacing=self.params)

    # ------------------------------------------------------------- programs
    def _jit(self, fn, name: str, **jit_kwargs):
        """``jax.jit`` or the ledger's observed jit — the ONE dispatch
        point deciding whether compiles are measured, and the ONE place
        every compiled-program host call is counted (``dispatch_count``,
        the denominator behind ``serve_dispatches``: the multi-step win
        is fewer of exactly these).  With no ledger the builtin runs
        underneath, so the flag-off compiled-program set is byte-
        identical (the parity pin — the counting closure is host Python,
        it compiles nothing).  A program's first call traces, lowers and
        compiles it (or loads it from the compile cache): that call runs
        under a ``program_build`` span, which is how set-up time is split
        by program."""
        if self._ledger is None:
            compiled = jax.jit(fn, **jit_kwargs)
        else:
            compiled = self._ledger.jit(fn, name=name, **jit_kwargs)
        built = False

        def dispatch(*args, **kwargs):
            nonlocal built
            self.dispatch_count += 1
            if built:
                return compiled(*args, **kwargs)
            built = True
            with self.tracer.span("program_build", program=name):
                return compiled(*args, **kwargs)

        return dispatch

    def _sample(self, logits, rng):
        """(B, V) logits → (B,) token ids; greedy or temperature draw —
        the ONE sampling definition shared by prefill and decode."""
        if self.greedy:
            return logits.argmax(-1)
        return jax.random.categorical(
            rng, logits / max(self.temperature, 1e-6))

    def _build_step(self):
        dm = self.dm

        # a model whose call takes ``active`` is handed it: one with
        # per-slot state keeps the state of a slot that is not active bit
        # for bit, one with routed experts may keep such a slot's stale
        # token from every expert
        takes_active = "active" in inspect.signature(
            type(dm).__call__).parameters

        def hold(active) -> dict:
            return {"active": active} if takes_active else {}

        def step(params, cache, tokens, lengths, active, rng):
            # ROWS: write index = current length, written by the model:
            # models/gpt.py ``select_slot_row`` (a select fused into the
            # attention's pass over the donated table: no loop of row
            # writes, no copy of a table leaf: tests/test_tpu_compile.py),
            # the latent and hybrid models ``write_slot_rows``.
            # Inactive (free) slots write garbage into their own rows
            # only, which the next insert's prefill overwrites — validity
            # is length-driven, so stale positions are never attended —
            # and a slot freed at length max_len writes nothing: either
            # helper DROPS a position past the table.  STATE has no such
            # argument (a write IS an advance): the model keeps it where
            # ``active`` is false.  The advanced token AND length vectors
            # are program outputs so the next iteration can consume them
            # on device (`_dev_learn`) instead of re-uploading host
            # mirrors.
            logits, upd = dm.apply(
                {"params": params, "cache": cache}, tokens[:, None],
                train=False, positions=lengths[:, None], mutable=["cache"],
                **hold(active))
            nxt = self._sample(logits[:, -1], rng).astype(tokens.dtype)
            return (upd["cache"], jnp.where(active, nxt, tokens),
                    jnp.where(active, lengths + 1, lengths))

        def routed_step(params, cache, tokens, lengths, active, rng):
            """The step above for a model with routed experts, which also
            returns, over the ACTIVE slots' choices among the experts HELD
            here, ``[experts touched, summed over expert layers; largest
            count any expert received; (token, expert) pairs]``: three
            integers beside the tokens."""
            logits, upd = dm.apply(
                {"params": params, "cache": cache}, tokens[:, None],
                train=False, positions=lengths[:, None],
                mutable=["cache", "intermediates"], **hold(active))
            nxt = self._sample(logits[:, -1], rng).astype(tokens.dtype)
            choice = jnp.stack(jax.tree.leaves(upd["intermediates"]))
            counts = jnp.sum(
                jax.nn.one_hot(choice, dm.num_experts, dtype=jnp.int32)
                * active[None, :, None, None].astype(jnp.int32), axis=(1, 2))
            first, n = getattr(dm, "experts_held", None) \
                or (0, dm.num_experts)
            counts = counts[:, first:first + n]
            routing = jnp.stack([jnp.sum(counts > 0), jnp.max(counts),
                                 jnp.sum(counts)])
            return (upd["cache"], jnp.where(active, nxt, tokens),
                    jnp.where(active, lengths + 1, lengths), routing)

        if self.expert_layers:
            return self._jit(routed_step, "kv_decode_step_routed",
                             donate_argnums=1)
        return self._jit(step, "kv_decode_step", donate_argnums=1)

    def _prefill(self, lpad: int):
        """Compiled prefill-insert for one padded prompt length.

        Slices slot ``slot`` out of every cache leaf and makes ONE call of
        the served module over the padded prompt from position 0 (batch 1;
        ``prompt_len`` marks the call: the block attends within itself and
        writes the slot's rows ``[0, lpad)`` in one piece), writes the
        slice back, and samples the FIRST generated token from the logits
        the module returns, which are those of the last REAL prompt
        position alone.  ROWS: pad positions past ``prompt_len`` write
        garbage K/V beyond the slot's length — invisible under the length
        mask and overwritten as decoding advances (the same argument that
        makes free-slot writes safe); rows at or past ``lpad`` are left as
        they were.  STATE: the model starts it from zero and makes the
        pads inert (module docstring), so the slice written back is the
        state after ``prompt_len`` tokens whatever the slot held.  The
        decode step is untouched: admission never recompiles it.

        A model that holds a SHARE of its experts (``experts_held``) also
        returns the (token, expert) pairs of the prompt's real tokens that
        fell to experts held here: what was computed."""
        dm = self.dm
        share = self.expert_layers and getattr(dm, "experts_held", None)

        def batched(params, cache, slot, tokens, prompt_len, rng):
            sub = jax.tree.map(
                lambda t: lax.dynamic_slice_in_dim(t, slot, 1, 0), cache)
            logits, upd = dm.apply(
                {"params": params, "cache": sub}, tokens[None, :],
                train=False,
                positions=jnp.arange(lpad, dtype=jnp.int32)[None, :],
                prompt_len=prompt_len[None],
                mutable=["cache", "intermediates"] if share else ["cache"])
            first = self._sample(logits[:, -1], rng)[0]
            cache = jax.tree.map(
                lambda full, s: lax.dynamic_update_slice_in_dim(
                    full, s, slot, 0), cache, upd["cache"])
            if not share:
                return cache, first.astype(tokens.dtype)
            choice = jnp.stack(jax.tree.leaves(upd["intermediates"]))
            lo, n = share
            held = ((choice >= lo) & (choice < lo + n)
                    & (jnp.arange(lpad) < prompt_len)[None, :, None])
            return cache, first.astype(tokens.dtype), jnp.sum(held)

        return self._jit(batched, f"kv_prefill_batched_l{lpad}",
                         donate_argnums=1)

    def _chunk(self, lpad: int):
        """Compiled chunk-resumable prefill for one padded CHUNK length.

        Where ``_prefill`` makes one call over a block from position 0,
        this scans the chunk through the single-token slot-decode step
        (batch 1) from a traced ``start`` position (positions
        ``start .. start+lpad-1``), so one compile per power-of-two chunk
        bucket serves every resume point — a long prompt's admission
        becomes several short scans the scheduler can interleave with
        decode iterations.  ``n_valid`` is the chunk's
        real token count; the sampled token (logits at the last valid
        position) only matters on the FINAL chunk — it is the request's
        first generated token, exactly as in the monolithic prefill.
        Padding past ``n_valid`` writes garbage K/V that the next chunk
        (which starts at ``start+n_valid``) or decode overwrites, and
        pad rows whose position runs past ``max_len`` are dropped — for
        this one-token path the drop rule lives in models/gpt.py
        ``select_slot_row`` (a position past the table equals no index of
        the ``(1, max_len, ...)`` sub-table; tests/test_serving.py holds
        it against a clamping write, which would overwrite the real token
        at ``max_len - 1``), for int8 storage in ``write_slot_rows``."""
        dm = self.dm

        def chunk(params, cache, slot, tokens, start, n_valid, rng):
            sub = jax.tree.map(
                lambda t: lax.dynamic_slice_in_dim(t, slot, 1, 0), cache)

            def body(c, xs):
                tok, t = xs
                logits, upd = dm.apply(
                    {"params": params, "cache": c}, tok[None, None],
                    train=False, positions=t[None, None],
                    mutable=["cache"])
                return upd["cache"], logits[0, -1]

            sub, all_logits = lax.scan(
                body, sub,
                (tokens, start + jnp.arange(lpad, dtype=jnp.int32)))
            last = jnp.take(all_logits, n_valid - 1, axis=0)
            first = self._sample(last[None, :], rng)[0]
            cache = jax.tree.map(
                lambda full, s: lax.dynamic_update_slice_in_dim(
                    full, s, slot, 0), cache, sub)
            return cache, first.astype(tokens.dtype)

        return self._jit(chunk, f"kv_prefill_chunk_l{lpad}",
                         donate_argnums=1)

    def _verify(self, width: int):
        """Compiled speculative-verify step for one (slots, width) token
        block: per slot, ``width`` consecutive tokens (the committed
        pending token + width-1 draft proposals) enter at positions
        ``length .. length+width-1``; every position's K/V is written into
        the cache and every position's logits take their greedy argmax in
        ONE batched slot-decode-style program (the models/gpt.py
        token-block contract — each query masked to positions ≤ its own).
        The host then ACCEPTS the longest draft prefix matching the
        argmaxes (``commit_block``); rejected positions stay in the
        buffer but are invalidated by length bookkeeping alone.  Greedy
        only: greedy acceptance is what makes speculative output bitwise
        identical to non-speculative decode."""
        dm = self.dm

        def verify(params, cache, block, lengths):
            positions = (lengths[:, None]
                         + jnp.arange(width, dtype=jnp.int32)[None, :])
            logits, upd = dm.apply(
                {"params": params, "cache": cache}, block,
                train=False, positions=positions, mutable=["cache"])
            return upd["cache"], logits.argmax(-1).astype(block.dtype)

        return self._jit(verify, f"kv_verify_w{width}", donate_argnums=1)

    def _block_ops(self):
        """Jitted prefix-pool block copy programs, compiled once each
        (slot/start are traced): ``read`` slices one block of a slot's KV
        out of every cache leaf; ``write`` scatters a pooled block back
        into a (possibly different) slot.  Cache leaves in slot-decode
        mode are (slots, max_len, kv_heads, head_dim) K/V buffers plus —
        under int8 storage — (slots, max_len, kv_heads) scale leaves, so
        the slices cover whatever trails the (slot, position) dims."""
        blk = self.prefix_block

        def read(cache, slot, start):
            return jax.tree.map(
                lambda t: lax.dynamic_slice(
                    t, (slot, start) + (0,) * (t.ndim - 2),
                    (1, blk) + t.shape[2:]), cache)

        def write(cache, entry, slot, start):
            return jax.tree.map(
                lambda t, e: lax.dynamic_update_slice(
                    t, e.astype(t.dtype),
                    (slot, start) + (0,) * (t.ndim - 2)),
                cache, entry)

        return (self._jit(read, "kv_prefix_read_block"),
                self._jit(write, "kv_prefix_write_block", donate_argnums=0))

    def _handoff_block(self) -> int:
        """Block granularity of the handoff transfer format.  Prefers the
        prefix-pool block size (so a handoff payload is the same shape a
        pool entry would be) but falls back to one whole-row block when
        ``prefix_block`` does not divide ``max_len`` — a partial tail
        block would make ``dynamic_slice`` clamp its start and silently
        read shifted positions."""
        return (self.prefix_block if self.max_len % self.prefix_block == 0
                else self.max_len)

    def _handoff_ops(self):
        """Jitted handoff block copy programs (compiled once each;
        slot/start are traced) — the ``_block_ops`` machinery pointed at
        the disaggregated prefill→decode transfer: ``read`` slices one
        handoff block of a slot's KV out of every cache leaf, ``write``
        scatters a transferred block into the receiving table's slot.
        int8 scale leaves are cache leaves like any other, so they ride
        the same tree map and the restored KV is byte-exact."""
        hb = self._handoff_block()

        def read(cache, slot, start):
            return jax.tree.map(
                lambda t: lax.dynamic_slice(
                    t, (slot, start) + (0,) * (t.ndim - 2),
                    (1, hb) + t.shape[2:]), cache)

        def write(cache, entry, slot, start):
            return jax.tree.map(
                lambda t, e: lax.dynamic_update_slice(
                    t, e.astype(t.dtype),
                    (slot, start) + (0,) * (t.ndim - 2)),
                cache, entry)

        return (self._jit(read, "kv_handoff_read_block"),
                self._jit(write, "kv_handoff_write_block",
                          donate_argnums=0))

    # ------------------------------------------------------------ slot API
    @property
    def free_slots(self) -> list[int]:
        return [i for i in range(self.slots)
                if not (self.active[i] or self.reserved[i])]

    def _put_vec(self, arr):
        arr = jnp.asarray(arr)
        if self._vec_sharding is not None:
            arr = jax.device_put(arr, self._vec_sharding)
        return arr

    def _put_repl(self, arr):
        """Replicated placement: the padded prompt is per-scan-step data,
        not a (slots,) vector — slot sharding would demand the padded
        length divide the data axis (it usually won't)."""
        arr = jnp.asarray(arr)
        if self.mesh is not None:
            arr = jax.device_put(arr, NamedSharding(self.mesh, P()))
        return arr

    def _dev_cached(self, name: str, host, put=None):
        """Device copy of a host slot vector, re-uploaded only when the
        host VALUE changed since the copy was learned — the k=1 decode
        loop, the draft table and the fused multi-step dispatch all stop
        paying a per-iteration H2D upload for tokens/lengths/mask.  The
        cache is value-keyed, not identity-keyed: any host-side edit
        (admission, evict, commit_block, rewind) is caught by comparison
        at the next dispatch, which IS the explicit host→device sync
        point."""
        host = np.asarray(host)
        hit = self._dev_vecs.get(name)
        if hit is not None and hit[0].shape == host.shape \
                and np.array_equal(hit[0], host):
            return hit[1]
        dev = (self._put_vec if put is None else put)(host)
        self._dev_vecs[name] = (host.copy(), dev)
        return dev

    def _dev_learn(self, name: str, host, dev) -> None:
        """Adopt a program OUTPUT as the device copy for ``name``: the
        caller updated the host mirror to the same value, so the next
        ``_dev_cached`` hit costs zero uploads."""
        self._dev_vecs[name] = (np.asarray(host).copy(), dev)

    def _next_rng(self):
        if self.greedy:
            return self._rng  # unused by the program; keep it static
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _multi_rngs(self, k: int):
        """(k,)-stacked per-iteration rng keys for a fused dispatch.
        Greedy replicates the static key (the program never reads it);
        sampling advances the split chain exactly as k single ``advance``
        calls would — the parity requirement."""
        if self.greedy:
            return jnp.stack([self._rng] * k)
        return jnp.stack([self._next_rng() for _ in range(k)])

    def _claim_slot(self, prompt, slot: int | None) -> tuple[np.ndarray,
                                                             int, int]:
        """Shared admission validation: returns (prompt, lp, slot)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        lp = int(prompt.shape[0])
        if lp < 1:
            raise ValueError("prompt must hold at least one token")
        if lp >= self.max_len:
            raise ValueError(
                f"prompt length {lp} leaves no room to generate within "
                f"max_len={self.max_len}")
        if slot is None:
            free = self.free_slots
            if not free:
                raise RuntimeError("no free slot — evict before inserting")
            slot = free[0]
        elif self.active[slot] or self.reserved[slot]:
            raise RuntimeError(f"slot {slot} is active — evict it first")
        self._reset_multi_slot(slot)
        return prompt, lp, slot

    def _reset_multi_slot(self, slot: int) -> None:
        """Clear a slot's multi-step decode state at (re)claim and evict:
        no EOS armed, unlimited budget, not device-halted.  ``_inflight``
        is deliberately NOT cleared — it balances dispatch (+k on the
        dispatch mask) against drain (-k on the same mask), and a slot
        reclaimed while a round is still outstanding must keep its
        pending decrement (the count is a conservative upper bound on
        outstanding device writes, which is all coverage needs)."""
        self.eos_tok[slot] = -1
        self.budget[slot] = 0
        self.halted[slot] = False

    def set_decode_limits(self, slot: int, eos: int | None,
                          budget: int) -> None:
        """Arm in-device deactivation for ``slot``: the fused multi-step
        program stops advancing it once it emits ``eos`` (None = never)
        or exhausts ``budget`` further emissions (0 = unlimited — the
        draft table's mode).  Host-side bookkeeping only; the vectors
        ride the next dispatch as value-cached operands."""
        if budget < 0:
            raise ValueError(f"budget must be >= 0, got {budget}")
        self.eos_tok[slot] = -1 if eos is None else int(eos)
        self.budget[slot] = int(budget)
        self.halted[slot] = False

    def insert(self, prompt, slot: int | None = None) -> tuple[int, int]:
        """Admit a prompt into a free slot (jitted prefill-insert).

        Returns ``(slot, first_token)`` — the first generated token is
        sampled by the prefill itself (its wall time IS the time-to-first-
        token), and the slot's length becomes ``len(prompt)``: the first
        decode step will write the returned token's K/V at that position.

        With the prefix pool enabled, admission routes through the
        chunk-resumable program (``begin_insert`` + one full-remainder
        ``prefill_chunk``) so prefill can start at the first uncached
        block; with the pool off, it is the one-call block program of
        ``_prefill``.
        """
        if self.prefix_cache_blocks:
            slot, _ = self.begin_insert(prompt, slot)
            try:
                first = self.prefill_chunk(slot)
            except BaseException:
                # the reservation is internal to this call — release
                # whichever state the slot reached so a failed admission
                # cannot leak it (a failure INSIDE the final chunk may
                # land after the slot already activated, e.g. in
                # _pool_prefix; aborting a no-longer-pending slot would
                # raise over the real error)
                if self.has_pending(slot):
                    self.abort_insert(slot)
                elif self.active[slot]:
                    self.evict(slot)
                raise
            assert first is not None  # uncapped chunk = whole remainder
            return slot, first
        prompt, lp, slot = self._claim_slot(prompt, slot)
        lpad = _bucket(lp, self.prefill_bucket, self.max_len)
        padded = np.zeros(lpad, np.int32)
        padded[:lp] = prompt
        if lpad not in self._prefills:
            self._prefills[lpad] = self._prefill(lpad)
        fn = self._prefills[lpad]
        t0 = time.perf_counter()
        self.cache, first, *held = fn(
            self.params, self.cache, jnp.int32(slot),
            self._put_repl(padded), jnp.int32(lp), self._next_rng())
        # the host reads the token here, inside the timed region:
        # prefill_s covers the program, not its enqueue
        first = int(first)
        self._phase_s["prefill_s"] += time.perf_counter() - t0
        self.prefill_tokens_computed += lp
        self.prefill_tokens_padded += lpad
        self.ssm_scan_positions += lpad * self.scan_layers
        self.ssm_scan_tokens += lp * self.scan_layers
        if held:        # a share of the experts: the program counted
            self.expert_assignments += int(held[0])
        elif self.expert_layers:    # every expert held: every choice
            self.expert_assignments += (
                lp * self.expert_layers * self.dm.experts_per_token)
        self.active[slot] = True
        self.lengths[slot] = lp
        self.tokens[slot] = first
        return slot, first

    # ------------------------------------------- chunked (resumable) prefill
    def begin_insert(self, prompt,
                     slot: int | None = None) -> tuple[int, int]:
        """Claim a slot for a chunk-by-chunk admission; returns
        ``(slot, reused_tokens)``.

        The slot is RESERVED (not free, not decoded) until the final
        ``prefill_chunk`` activates it.  With the prefix pool enabled, the
        longest cached block-aligned prefix is copied into the slot here
        and ``reused_tokens`` positions are skipped — prefill resumes at
        the first uncached block.  At least the prompt's final token is
        always computed (its logits sample the first generated token)."""
        self._scan_model_only("chunked (resumable) prefill")
        prompt, lp, slot = self._claim_slot(prompt, slot)
        reused = self._restore_prefix(prompt, lp, slot)
        self.reserved[slot] = True
        self.lengths[slot] = reused
        self._pending[slot] = {"prompt": prompt, "lp": lp, "filled": reused}
        return slot, reused

    def prefill_chunk(self, slot: int,
                      max_tokens: int | None = None) -> int | None:
        """Process the next ≤ ``max_tokens`` prompt tokens of a pending
        admission (one jitted chunk scan, compiled per power-of-two chunk
        bucket).  Returns the request's first generated token when this
        was the final chunk (the slot becomes active, exactly as after
        ``insert``), else None."""
        pend = self._pending.get(slot)
        if pend is None:
            raise RuntimeError(f"slot {slot} has no pending admission "
                               f"(begin_insert first)")
        filled, lp = pend["filled"], pend["lp"]
        n = lp - filled
        if max_tokens is not None:
            if max_tokens < 1:
                raise ValueError(
                    f"max_tokens must be positive, got {max_tokens}")
            n = min(n, int(max_tokens))
        final = filled + n == lp
        # chunk bucket floor is 1 (not prefill_bucket): budgets below the
        # admission floor must not round the chunk back up past the
        # scheduler's per-iteration token budget
        lpad = _bucket(n, 1, self.max_len)
        padded = np.zeros(lpad, np.int32)
        padded[:n] = pend["prompt"][filled:filled + n]
        if lpad not in self._chunks:
            self._chunks[lpad] = self._chunk(lpad)
        t0 = time.perf_counter()
        self.cache, first = self._chunks[lpad](
            self.params, self.cache, jnp.int32(slot),
            self._put_repl(padded), jnp.int32(filled), jnp.int32(n),
            self._next_rng())
        if final:
            # materialize the token BEFORE flipping host state: a deferred
            # device error surfaces here while the slot is still pending,
            # so the caller's abort path sees a consistent table — and
            # inside the timed region, so that the final chunk's prefill_s
            # covers the program (a chunk that is not final is read by
            # nobody and stays timed by its enqueue)
            first = int(first)
        self._phase_s["prefill_s"] += time.perf_counter() - t0
        pend["filled"] = filled + n
        self.lengths[slot] = filled + n
        self.prefill_tokens_computed += n
        self.prefill_tokens_padded += lpad
        if not final:
            return None
        del self._pending[slot]
        self.reserved[slot] = False
        self.active[slot] = True
        self.lengths[slot] = lp
        self.tokens[slot] = first
        self._pool_prefix(pend["prompt"], lp, slot)
        return first

    def pending_tokens(self, slot: int) -> int:
        """Prompt tokens a pending admission still has to prefill."""
        pend = self._pending[slot]
        return pend["lp"] - pend["filled"]

    def has_pending(self, slot: int) -> bool:
        """Whether ``slot`` holds an in-progress (begin_insert) admission."""
        return slot in self._pending

    def abort_insert(self, slot: int) -> None:
        """Release a reserved slot whose admission will not complete (the
        scheduler's mid-run-failure cleanup path)."""
        if slot not in self._pending:
            raise RuntimeError(f"slot {slot} has no pending admission")
        del self._pending[slot]
        self.reserved[slot] = False
        self.lengths[slot] = 0

    # ------------------------------------------------------- KV handoff
    def _claim_restore_slot(self, length: int, slot: int | None) -> int:
        """Shared restore-side validation (monolithic + paged): the
        restored sequence must leave room to decode, exactly insert's
        admission rule."""
        if not 1 <= length < self.max_len:
            raise ValueError(
                f"handoff length {length} must lie in [1, max_len="
                f"{self.max_len}) — a restored slot needs room to decode")
        if slot is None:
            free = self.free_slots
            if not free:
                raise RuntimeError(
                    "no free slot — evict before restoring a handoff")
            slot = free[0]
        elif self.active[slot] or self.reserved[slot]:
            raise RuntimeError(f"slot {slot} is active — evict it first")
        self._reset_multi_slot(slot)
        return slot

    def _check_handoff_payload(self, payload: dict, block: int) -> int:
        """Transfer-format compatibility gate: a payload restores only
        into a table with the same layout, block granularity, storage
        dtype and max_len — anything else would reinterpret bytes."""
        for key, want in (("layout", self.kv_layout),
                          ("block", block),
                          ("kv_dtype", self.kv_dtype),
                          ("max_len", self.max_len)):
            if payload.get(key) != want:
                raise ValueError(
                    f"handoff payload {key}={payload.get(key)!r} does not "
                    f"match the receiving table ({key}={want!r}): prefill "
                    f"and decode replicas must share the KV configuration")
        return int(payload["length"])

    def extract_handoff(self, slot: int) -> dict:
        """Serialize an active slot's KV state into a host-side transfer
        payload — the disaggregated-fleet handoff: a prefill replica
        extracts the finished prompt KV here and a decode replica
        ``restore_handoff``s it into its own table.

        The payload is a dict of plain host numpy trees (one per handoff
        block, sliced by the jitted ``_handoff_ops`` read program and
        ``device_get``; garbage positions past ``length`` in the final
        block travel along but are invisible — validity is length-driven
        on the receiving side too).  Under int8 storage the f32 scale
        leaves ride the same block trees, so restore is byte-exact and a
        greedy continuation on the decode replica is bitwise what the
        prefill replica would have produced.  The slot stays active:
        the caller evicts after a successful transfer."""
        self._scan_model_only("KV handoff")
        if not self.active[slot]:
            raise RuntimeError(f"slot {slot} is not active")
        if self._handoff_read is None:
            self._handoff_read, self._handoff_write = self._handoff_ops()
        hb = self._handoff_block()
        length = int(self.lengths[slot])
        blocks = []
        for start in range(0, length, hb):
            entry = self._handoff_read(
                self.cache, jnp.int32(slot), jnp.int32(start))
            blocks.append(jax.device_get(entry))
        return {"layout": self.kv_layout, "block": hb, "length": length,
                "token": int(self.tokens[slot]),
                "kv_dtype": self.kv_dtype, "max_len": self.max_len,
                "blocks": blocks}

    def restore_handoff(self, payload: dict,
                        slot: int | None = None) -> tuple[int, int]:
        """Admit a transferred KV payload into a free slot; returns
        ``(slot, first_token)`` exactly like ``insert`` — the first
        generated token was already sampled by the prefill replica and
        travels in the payload, so the receiving scheduler delivers it
        without running any program.  The slot comes up active at the
        transferred length and the next ``advance`` continues the
        sequence bitwise (same storage dtype both sides)."""
        self._scan_model_only("KV handoff")
        length = self._check_handoff_payload(payload, self._handoff_block())
        slot = self._claim_restore_slot(length, slot)
        if self._handoff_write is None:
            self._handoff_read, self._handoff_write = self._handoff_ops()
        hb = self._handoff_block()
        for b, entry in enumerate(payload["blocks"]):
            entry = jax.tree.map(self._put_repl, entry)
            self.cache = self._handoff_write(
                self.cache, entry, jnp.int32(slot), jnp.int32(b * hb))
        self.active[slot] = True
        self.lengths[slot] = length
        self.tokens[slot] = token = int(payload["token"])
        return slot, token

    # ------------------------------------------------------- prefix pool
    def _prefix_keys(self, prompt: np.ndarray, n_blocks: int):
        """Chained block keys: block b's key is SHA-256 of (block b-1's
        key ‖ block b's token bytes), so the 32-byte digest carries the
        FULL prefix identity — a block matches only when every block
        before it matched — at O(L) total work and constant key size
        (hashing the raw whole-prefix bytes per block would be O(L²)
        per admission and store megabytes of keys for long chains)."""
        blk = self.prefix_block
        keys, prev = [], b""
        for b in range(n_blocks):
            h = hashlib.sha256(prev)
            h.update(prompt[b * blk:(b + 1) * blk].tobytes())
            prev = h.digest()
            keys.append(prev)
        return keys

    def _restore_prefix(self, prompt: np.ndarray, lp: int,
                        slot: int) -> int:
        """Copy the longest cached block-aligned prefix into ``slot``;
        returns the number of reused token positions.  Reuse is capped at
        the blocks covering ``lp - 1`` tokens: the final prompt token is
        always recomputed so its logits can sample the first token."""
        if not self.prefix_cache_blocks:
            return 0
        blk = self.prefix_block
        usable = (lp - 1) // blk    # full blocks strictly before the tail
        insertable = lp // blk      # full blocks the prompt will pool
        keys = self._prefix_keys(prompt, usable)
        matched = 0
        for key in keys:
            if key not in self._prefix_pool:
                break
            matched += 1
        self.prefix_stats["hits"] += matched
        self.prefix_stats["misses"] += insertable - matched
        self.prefix_stats["tokens_reused"] += matched * blk
        if not matched:
            return 0
        if self._write_block is None:
            self._read_block, self._write_block = self._block_ops()
        for b, key in enumerate(keys[:matched]):
            self._prefix_pool.move_to_end(key)   # LRU touch
            self.cache = self._write_block(
                self.cache, self._prefix_pool[key], jnp.int32(slot),
                jnp.int32(b * blk))
        return matched * blk

    def _pool_prefix(self, prompt: np.ndarray, lp: int, slot: int) -> None:
        """After a completed prefill, pool every full block of the prompt
        not already cached (extracted from the slot's freshly-written KV),
        evicting least-recently-used entries past the pool bound."""
        if not self.prefix_cache_blocks:
            return
        blk = self.prefix_block
        if self._read_block is None:
            self._read_block, self._write_block = self._block_ops()
        for b, key in enumerate(self._prefix_keys(prompt, lp // blk)):
            if key in self._prefix_pool:
                self._prefix_pool.move_to_end(key)
                continue
            entry = self._read_block(
                self.cache, jnp.int32(slot), jnp.int32(b * blk))
            if self.mesh is not None:
                # pool entries replicate: a block extracted from one data
                # shard's slot row gets written into ANY slot later, so
                # leaving it pinned to the source shard would force XLA
                # into a resharding rematerialization on every hit
                repl = NamedSharding(self.mesh, P())
                entry = jax.tree.map(
                    lambda t: jax.device_put(t, repl), entry)
            self._prefix_pool[key] = entry
            self.prefix_stats["inserted_blocks"] += 1
        while len(self._prefix_pool) > self.prefix_cache_blocks:
            self._prefix_pool.popitem(last=False)
            self.prefix_stats["evictions"] += 1

    def prefix_cache_stats(self) -> dict | None:
        """Cumulative hit/miss/evict accounting (None when the pool is
        off).  ``hit_rate`` is block-level: reused blocks over reusable +
        pooled blocks."""
        if not self.prefix_cache_blocks:
            return None
        s = dict(self.prefix_stats)
        total = s["hits"] + s["misses"]
        s["cached_blocks"] = len(self._prefix_pool)
        s["hit_rate"] = s["hits"] / total if total else 0.0
        return s

    def reset_prefix_cache(self) -> None:
        """Drop pooled blocks and zero the accounting, so that a window
        starts on a cold pool and its hit rate is deterministic."""
        self._prefix_pool.clear()
        for k in self.prefix_stats:
            self.prefix_stats[k] = 0

    def advance(self, only=None) -> np.ndarray:
        """One decode iteration: every ACTIVE slot consumes its last token
        and emits the next one; lengths advance by one.  Returns the
        (slots,) token vector — inactive rows carry their stale token.
        The jitted step is compiled exactly once per cache shape.

        ``only`` restricts the iteration to a (slots,) bool subset of the
        active slots (the speculative draft's catch-up step: after a
        fully-accepted round only those slots must consume one more
        committed token).  Excluded slots keep their token and length.
        Their ROWS still receive a scatter write at their current length,
        which is invisible (length-driven validity) and overwritten by
        that slot's next real write: the free-slot-scatter argument, which
        holds for rows and for nothing else.  Their STATE is not written:
        the step is handed the mask and keeps it bit for bit, for an
        excluded live slot as for a free one.

        Two spans on ``self.tracer`` split the call where the device can
        wait for the host: ``step_dispatch`` around the whole ``_step``
        statement (the slot vectors' look-ups with their uploads, the
        call's argument handling and enqueue) and ``token_fetch`` around
        every ``np.asarray`` of its outputs (the tokens and, for a routed
        step, the routing integers)."""
        mask = self.active if only is None else np.asarray(only, np.bool_)
        live = self.lengths[mask]
        if live.size and int(live.max()) >= self.max_len:
            raise SlotOverflow(
                f"active slot at length {int(live.max())} would write past "
                f"max_len={self.max_len}; the scheduler must bound "
                f"prompt + max_new_tokens at admission")
        if self._multi_pending:
            raise RuntimeError(
                "a fused multi-step round is in flight — drain it before "
                "a single-step advance (host mirrors lag the device)")
        t0 = time.perf_counter()
        with self.tracer.span("step_dispatch"):
            self.cache, d_nxt, d_len, *routing = self._step(
                self.params, self.cache,
                self._dev_cached("tokens", self.tokens),
                self._dev_cached("lengths", self.lengths),
                self._dev_cached("mask", mask), self._next_rng())
        with self.tracer.span("token_fetch"):
            nxt = np.asarray(d_nxt)
            if routing:
                touched, load_max, pairs = (
                    int(v) for v in np.asarray(routing[0]))
                self.last_routing = {
                    "experts_touched": touched / self.expert_layers,
                    "expert_load_max": load_max}
                self.expert_assignments += pairs
        self._phase_s["decode_s"] += time.perf_counter() - t0
        self.lengths[mask] += 1
        self.tokens = nxt.astype(np.int32)
        # the step's own outputs ARE the next iteration's inputs — learn
        # them so an uninterrupted decode loop uploads nothing
        self._dev_learn("tokens", self.tokens, d_nxt)
        self._dev_learn("lengths", self.lengths, d_len)
        return nxt

    # ------------------------------------------------- multi-step decode
    def _multi(self, k: int):
        """Fused k-iteration decode program (the serving twin of PR 1's
        ``build_many_step``): one ``lax.scan`` of k decode steps with
        token feedback, lengths, active mask and per-slot budgets carried
        ON DEVICE, plus in-device deactivation — a slot that emits its
        armed EOS token, exhausts its emission budget, or reaches max_len
        leaves the carried mask and contributes nothing to later
        iterations.  The prologue folds the host-edit merge in: per-slot
        ``edited`` flags select the freshly-uploaded host vectors over
        the device-carried ones, so scheduler edits between dispatches
        (admission, evict) need no separate merge program and no D2H
        wait.  Returns the final carry plus (k, slots) stacks of the
        emitted tokens, the active-at-entry mask per iteration (a
        contiguous True prefix per slot — deactivation only turns slots
        off) and the deactivated-at flags."""
        dm = self.dm
        max_len = self.max_len

        def multi(params, cache, d_tok, d_len, d_act, d_bud,
                  h_tok, h_len, h_act, h_bud, edited, eos, rngs):
            tokens = jnp.where(edited, h_tok, d_tok)
            lengths = jnp.where(edited, h_len, d_len)
            active = jnp.where(edited, h_act, d_act)
            budget = jnp.where(edited, h_bud, d_bud)

            def body(carry, rng):
                cache, tokens, lengths, active, budget = carry
                logits, upd = dm.apply(
                    {"params": params, "cache": cache}, tokens[:, None],
                    train=False, positions=lengths[:, None],
                    mutable=["cache"])
                nxt = self._sample(logits[:, -1],
                                   rng).astype(tokens.dtype)
                nxt = jnp.where(active, nxt, tokens)
                nlen = jnp.where(active, lengths + 1, lengths)
                nbud = jnp.where(active & (budget > 0),
                                 budget - 1, budget)
                done = active & ((nxt == eos)
                                 | ((budget > 0) & (nbud <= 0))
                                 | (nlen >= max_len))
                return ((upd["cache"], nxt, nlen, active & ~done, nbud),
                        (nxt, active, done))

            carry, (toks, acts, dones) = lax.scan(
                body, (cache, tokens, lengths, active, budget), rngs)
            return carry, toks, acts, dones

        return self._jit(multi, f"kv_decode_multi_k{k}", donate_argnums=1)

    def _multi_prepare(self, mask: np.ndarray, k: int) -> tuple:
        """Layout hook before a fused dispatch: extra program operands
        plus writability guarantees (the paged table overrides this to
        cover in-flight growth and snapshot the block table)."""
        return ()

    def dispatch_multi(self, k: int) -> dict:
        """Issue one fused k-iteration decode round WITHOUT materializing
        its results: the token/mask stacks start their D2H copy
        asynchronously and the device carry stays resident for the next
        round's prologue — the scheduler overlaps host work (admissions,
        chunk prefill, delivery of the previous round) with this round's
        device time, then ``drain_multi`` blocks only on the copy.
        Outstanding rounds drain strictly in dispatch order (FIFO).
        Slots the device already deactivated (``halted``) are excluded
        from the host mask; fresh host-side edits ride as ``edited``-
        selected uploads."""
        self._scan_model_only("multi-step decode")
        if k < 1:
            raise ValueError(f"multi-step k must be >= 1, got {k}")
        if k not in self._multis:
            self._multis[k] = self._multi(k)
        mask = self.active & ~self.halted
        extra = self._multi_prepare(mask, k)
        self._inflight[mask] += k
        h_tok = self.tokens.astype(np.int32)
        h_len = self.lengths.astype(np.int32)
        h_act = mask.astype(np.bool_)
        h_bud = self.budget.astype(np.int32)
        snap = self._multi_snap
        if self._multi_state is None or snap is None:
            # first dispatch: the host view is the only truth — the
            # device operands are the same upload, fully selected
            edited = np.ones(self.slots, np.bool_)
            d_tok = self._put_vec(h_tok)
            d_len = self._put_vec(h_len)
            d_act = self._put_vec(h_act)
            d_bud = self._put_vec(h_bud)
        else:
            # edited = host diverged from the host-view-at-last-dispatch
            # snapshot; drain applies round deltas to BOTH sides of this
            # comparison, so only genuine scheduler edits re-upload
            edited = ((h_tok != snap["tokens"])
                      | (h_len != snap["lengths"])
                      | (h_act != snap["mask"])
                      | (h_bud != snap["budget"]))
            d_tok, d_len, d_act, d_bud = self._multi_state
        t0 = time.perf_counter()
        carry, toks, acts, dones = self._multis[k](
            self.params, self.cache, d_tok, d_len, d_act, d_bud,
            self._put_vec(h_tok), self._put_vec(h_len),
            self._put_vec(h_act), self._put_vec(h_bud),
            self._put_vec(edited),
            self._dev_cached("eos", self.eos_tok),
            *extra, self._multi_rngs(k))
        self.cache = carry[0]
        self._multi_state = tuple(carry[1:])
        for arr in (toks, acts, dones):
            if hasattr(arr, "copy_to_host_async"):
                arr.copy_to_host_async()
        self._phase_s["decode_s"] += time.perf_counter() - t0
        self._multi_snap = {"tokens": h_tok.copy(), "lengths": h_len.copy(),
                            "mask": h_act.copy(), "budget": h_bud.copy()}
        handle = {"k": int(k), "mask": h_act.copy(),
                  "tok": toks, "act": acts, "done": dones}
        self._multi_pending.append(handle)
        return handle

    def drain_multi(self, handle: dict | None = None
                    ) -> tuple[np.ndarray, np.ndarray]:
        """Materialize the in-flight fused round and fold its deltas into
        the host mirrors: lengths advance by each slot's emitted count,
        ``tokens`` takes the last emission, deactivated slots set
        ``halted``.  The SAME deltas land on the dispatch snapshot, so
        the next dispatch's ``edited`` comparison sees only scheduler
        edits.  Returns ``(toks, acts)`` — (k, slots) stacks of tokens
        and the active-at-entry mask per iteration (``acts[:, s]`` is a
        contiguous True prefix: ``acts.sum(0)`` emissions, the last at
        row ``emitted-1``)."""
        if not self._multi_pending:
            raise RuntimeError("no fused round in flight")
        if handle is not None and handle is not self._multi_pending[0]:
            raise RuntimeError(
                "fused rounds drain in dispatch order — this handle is "
                "not the oldest outstanding round")
        handle = self._multi_pending.pop(0)
        k, mask = handle["k"], handle["mask"]
        t0 = time.perf_counter()
        toks = np.asarray(handle["tok"]).astype(np.int32)
        acts = np.asarray(handle["act"]).astype(np.bool_)
        dones = np.asarray(handle["done"]).astype(np.bool_)
        self._phase_s["decode_s"] += time.perf_counter() - t0
        self._inflight[mask] -= k
        emitted = acts.sum(axis=0).astype(np.int32)
        sel = emitted > 0
        done_any = dones.any(axis=0)
        snap = self._multi_snap
        # slots the scheduler touched mid-flight (evict + readmit) were
        # device-inactive the whole round — host-finish conditions ARE
        # the in-device deactivation conditions — so ``sel`` only covers
        # slots whose host state still describes this round's stream
        for host, view in ((self.lengths, snap["lengths"]),):
            host[sel] += emitted[sel]
            view[sel] += emitted[sel]
        last = toks[np.maximum(emitted - 1, 0), np.arange(self.slots)]
        self.tokens[sel] = last[sel]
        snap["tokens"][sel] = last[sel]
        bsel = sel & (self.budget > 0)
        self.budget[bsel] -= emitted[bsel]
        snap["budget"][bsel] -= emitted[bsel]
        self.halted |= done_any
        snap["mask"] &= ~done_any
        return toks, acts

    def advance_multi(self, k: int) -> tuple[np.ndarray, np.ndarray]:
        """Fused k decode iterations, synchronously: one host dispatch,
        one D2H materialization — ``dispatch_multi`` + ``drain_multi``
        back to back (the speculative draft's proposal loop and tests
        use this; the scheduler pipeline splits the two)."""
        self.dispatch_multi(k)
        return self.drain_multi()

    @property
    def pending_multi(self) -> int:
        """Outstanding (dispatched, undrained) fused rounds."""
        return len(self._multi_pending)

    def abandon_multi(self) -> None:
        """Drop every outstanding fused round without folding its tokens
        into the host mirrors (run()'s failure cleanup: the window's
        results are lost anyway, but evict() must not race a half-drained
        round's bookkeeping).  Rebalances ``_inflight`` for each dropped
        handle and resets the device carry — the next dispatch re-uploads
        from the host mirrors (edited = all-True)."""
        for handle in self._multi_pending:
            self._inflight[handle["mask"]] -= handle["k"]
        self._multi_pending.clear()
        self._multi_state = None
        self._multi_snap = None

    # ------------------------------------------------- speculative decode
    def verify_block(self, block) -> np.ndarray:
        """Score a (slots, width) token block in one batched step and
        return the (slots, width) per-position greedy argmax tokens.

        Per slot, ``block[s] = [pending_token, d_1, .., d_{width-1}]`` —
        the committed pending token followed by draft proposals; K/V for
        all ``width`` positions is written at ``length .. length+width-1``
        and the returned row ``g`` satisfies: ``g[j]`` is the target's
        greedy token after consuming ``block[s, :j+1]``.  Greedy
        acceptance (``commit_block``) then takes the longest prefix with
        ``d_{j+1} == g[j]`` plus the target's own next token — bitwise
        what non-speculative decode would have emitted.  Host bookkeeping
        (lengths/tokens) is NOT touched here: the scheduler owns
        acceptance, and rejected positions are rolled back by length
        bookkeeping alone (no KV rewrite)."""
        self._scan_model_only("speculative verify")
        if not self.greedy:
            raise ValueError(
                "verify_block requires greedy sampling: the exact "
                "acceptance rule (accept while draft == target argmax) "
                "only exists for greedy decode")
        block = np.asarray(block, np.int32)
        if block.ndim != 2 or block.shape[0] != self.slots:
            raise ValueError(
                f"block must be (slots, width) = ({self.slots}, k+1), "
                f"got {block.shape}")
        width = int(block.shape[1])
        live = self.lengths[self.active]
        if live.size and int(live.max()) + width > self.max_len:
            raise SlotOverflow(
                f"verify width {width} at length {int(live.max())} would "
                f"write past max_len={self.max_len}; the scheduler must "
                f"cap the draft k by remaining slot capacity")
        if width not in self._verifies:
            self._verifies[width] = self._verify(width)
        blk = jnp.asarray(block)
        if self._blk_sharding is not None:
            blk = jax.device_put(blk, self._blk_sharding)
        t0 = time.perf_counter()
        self.cache, g = self._verifies[width](
            self.params, self.cache, blk,
            self._dev_cached("lengths", self.lengths))
        g = np.asarray(g).astype(np.int32)
        self._phase_s["decode_s"] += time.perf_counter() - t0
        return g

    def commit_block(self, slot: int, n: int, last_token: int) -> None:
        """Commit ``n`` verified positions of the last ``verify_block``
        into ``slot``: lengths advance by ``n`` and ``last_token`` (the
        target's own token at the acceptance point) becomes the slot's
        pending token.  This IS the rollback path for rejected draft
        positions: the verify wrote K/V for the whole block, but validity
        is length-driven, so advancing by only the accepted count
        invalidates the rejected tail with no KV rewrite — the slot's
        next write simply lands over it."""
        self._rows_only("commit_block")
        if not self.active[slot]:
            raise RuntimeError(f"slot {slot} is not active")
        if n < 1:
            raise ValueError(f"commit_block needs n >= 1, got {n}")
        if int(self.lengths[slot]) + n > self.max_len:
            raise SlotOverflow(
                f"committing {n} positions at length "
                f"{int(self.lengths[slot])} exceeds max_len={self.max_len}")
        self.lengths[slot] += n
        self.tokens[slot] = int(last_token)

    def rewind(self, slot: int, length: int, token: int) -> None:
        """Rewind a slot's validity to ``length`` and set its pending
        token — the DRAFT table's resync after a verify round: positions
        past ``length`` were speculative writes, invalidated here by
        length bookkeeping alone.  A rewind can never extend validity."""
        self._rows_only("rewind")
        if not self.active[slot]:
            raise RuntimeError(f"slot {slot} is not active")
        if length > int(self.lengths[slot]):
            raise ValueError(
                f"rewind cannot extend validity: slot {slot} is at "
                f"{int(self.lengths[slot])}, asked for {length}")
        self.lengths[slot] = int(length)
        self.tokens[slot] = int(token)
        # a rewind shrinks validity below any max_len halt the fused
        # draft rounds may have recorded — the slot decodes again
        self.halted[slot] = False

    def evict(self, slot: int) -> None:
        """Free a slot.  Pure host bookkeeping.  Stale ROWS stay in the
        buffer but are unreachable (validity is length-driven) and the
        next insert's prefill overwrites them from position 0.  Stale
        STATE stays too, frozen (the step keeps it while the slot is not
        active), and is never read: the next insert's prefill starts from
        zero and writes the whole leaf."""
        if not self.active[slot]:
            raise RuntimeError(f"slot {slot} is not active")
        self.active[slot] = False
        self.lengths[slot] = 0
        self.tokens[slot] = 0
        self._reset_multi_slot(slot)

    def phase_times(self) -> dict[str, float]:
        """Cumulative host-observed seconds inside the compiled prefill
        (monolithic + chunk) and decode programs — the device-side phase
        timestamps behind the scheduler's ``device_phase_s`` split.  Host-
        observed: each program's result is materialized before the next
        scheduling decision, so dispatch + device wait both land here."""
        return dict(self._phase_s)

    def counters(self) -> dict[str, int]:
        """Cumulative counts beside ``phase_times``: prompt tokens fed
        through a prefill program and the positions those programs ran
        (pads included), the (token, expert) routing assignments made to
        experts held here (0 for a model without experts), what one token
        of one slot keeps in the table's full-length per-position rows,
        all layers together, what one slot keeps as per-slot state
        whatever its length (0 for a model without state), what it keeps
        in ring rows whatever its length (0 for a model without rings),
        and the bucket positions and the prompt tokens, each times the
        layers, that went through the selective-scan kernel (0 for a model
        that has none)."""
        _, state, rings = self._table_bytes()
        return {"prefill_tokens_computed": self.prefill_tokens_computed,
                "prefill_tokens_padded": self.prefill_tokens_padded,
                "expert_assignments": self.expert_assignments,
                # (the paged layout counts the blocks that back live slots)
                "cache_bytes_per_token":
                    (self.kv_bytes_per_slot()
                     - (state + rings) // self.slots) // self.max_len,
                "state_bytes_per_slot": state // self.slots,
                "window_bytes_per_slot": rings // self.slots,
                "ssm_scan_positions": self.ssm_scan_positions,
                "ssm_scan_tokens": self.ssm_scan_tokens}

    def _table_bytes(self) -> tuple[int, int, int]:
        """Stored bytes of the whole table by kind of leaf: ``(full-length
        per-position rows, per-slot state, rings)``."""
        size = {"rows": 0, "state": 0, "rings": 0}
        for path, leaf in jax.tree_util.tree_leaves_with_path(self.cache):
            kind = "state" if path[-1].key in self.state_leaves else \
                "rings" if path[-1].key in self.ring_leaves else "rows"
            size[kind] += int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
        return size["rows"], size["state"], size["rings"]

    def past_window(self) -> int:
        """Active slots whose length exceeds the rings' rows: whose window
        layers no longer hold every position."""
        ring = min(self.ring_leaves.values())
        return int(np.sum(self.lengths[self.active] > ring))

    def kv_bytes_per_slot(self) -> int:
        """Stored table bytes per serving slot: every cache leaf — K/V
        payload plus, under int8 storage, its f32 scale leaves, plus a
        model's per-slot state — divided by the slot count.  THE capacity
        number behind ``--serve-kv-dtype``: bf16 halves f32; int8 halves
        bf16's payload again, plus a per-written-vector scale overhead of
        4/head_dim (the serve section carries it as
        ``serve_kv_bytes_per_slot``, gated lower-is-better by `analyze
        diff`)."""
        return sum(self._table_bytes()) // self.slots

    def compiled_programs(self) -> dict[str, int]:
        """The recompile-freedom invariant the tests pin down: one decode
        step, one prefill program per power-of-two bucket, one chunk
        program per power-of-two CHUNK bucket, at most the two prefix
        block-copy programs, and one speculative-verify program per block
        width actually used.  With chunking, the prefix pool and
        speculative decoding off, the chunk/block/verify counts are 0 and
        the compiled set is exactly PR 7's."""
        out = {"decode_steps": 1,
               "prefill_buckets": len(self._prefills),
               "prefill_chunk_buckets": len(self._chunks),
               "prefix_block_ops": (0 if self._read_block is None else 2),
               "verify_widths": len(self._verifies),
               # one fused multi-step decode program per k actually
               # dispatched (--serve-multi-step) — 0 with the flag off:
               # the flag-off program set stays exactly the prior round's
               "decode_multi_widths": len(self._multis)}
        # the disaggregated handoff read/write pair appears only once a
        # handoff actually ran: with the feature off the compiled set —
        # keys included — is exactly the round-17 one (the flag-off
        # program-set parity pin)
        if self._handoff_read is not None:
            out["handoff_block_ops"] = 2
        return out

    def timeline_gauges(self) -> dict[str, float]:
        """Host-side gauge snapshot for the ``--timeline`` sampler: numpy
        sums over the slot table + dict lengths — NO device syncs (the
        cache leaves are touched only for shape/dtype metadata, cached
        after the first call).  ``kv_live_bytes`` is length-proportional
        stored bytes: tokens actually valid × stored bytes per token."""
        per_tok = getattr(self, "_tl_token_bytes", None)
        if per_tok is None:
            per_tok = self._tl_token_bytes = \
                self._table_bytes()[0] / (self.slots * self.max_len)
        live_tokens = int(self.lengths.sum())
        return {
            "kv_active_slots": int(self.active.sum()),
            "kv_reserved_slots": int(self.reserved.sum()),
            "kv_live_tokens": live_tokens,
            "kv_live_bytes": live_tokens * per_tok,
            "kv_prefix_pool_blocks": len(self._prefix_pool),
        }


class PagedSlotKVCache(SlotKVCache):
    """Paged KV layout (vLLM PagedAttention, arXiv:2309.06180): one
    physical block pool shared by every slot + host-owned per-slot block
    tables, selected by ``SlotKVCache(..., kv_layout="paged")``.

    What changes vs the monolithic table:

    * DEVICE: cache leaves are pools ``(num_blocks+1, kv_heads, block,
      head_dim)`` (+1 is a scratch block — see below) instead of
      ``(slots, max_len, ...)`` rows; the model's paged decode mode
      (models/gpt.py ``paged_blocks``) scatters each write through the
      block table and reads either fused (ops/paged_attention.py Pallas
      kernel — the decode/verify hot op) or by gather + dense (bitwise
      the monolithic math — the prefill scan).
    * HOST: block allocation, refcounts, and the block tables.  A
      prefix-pool hit is a POINTER WRITE — matched pool blocks are
      aliased into the slot's table with a refcount bump and zero KV
      bytes copied (counted in ``paged_stats``); the pool itself stores
      block IDS with a refcount pin, so each hot prefix exists exactly
      once in device memory.  The first write into a shared block
      triggers copy-on-write: one jitted block copy into a freshly
      allocated block, after which the writer owns its copy and the
      other sharers (and the pool) are untouched.
    * SAFETY: the pool carries one extra SCRATCH block (id
      ``num_blocks``); unmapped table entries point at it, and during
      decode/verify the device sees scratch-only table rows for
      non-participating slots — the monolithic layout's "garbage writes
      land in your own row" argument becomes "garbage writes land in
      scratch".  Out-of-range positions are dropped by construction
      (models/gpt.py routes them to an out-of-bounds offset, the scatter
      drop rule).
    * CAPACITY: ``kv_bytes_per_slot`` reports bytes actually backing
      live sequences — in-use pool blocks (payload + scales) + block
      tables, amortized over live slots (the BASELINE stored-bytes
      rule) — not ``slots × max_len``.  Admission is gated by
      ``can_admit`` (free blocks vs the request's worst-case block need
      plus committed-but-unallocated budgets of live slots); running the
      pool dry mid-flight raises ``BlockPoolExhausted``.

    Parity contract: prefill (gather path) is bitwise the monolithic
    prefill; fused decode/verify is tolerance-based (online-softmax
    reassociation — the int8 precedent).  ``paged_fused=False`` keeps
    even decode on the gather path (the parity oracle in paged clothes).
    """

    def __init__(self, model: nn.Module, params, slots: int, *,
                 mesh=None, greedy: bool = True, temperature: float = 1.0,
                 prefill_bucket: int = 8, rng=None, kv_dtype=None,
                 prefix_cache_blocks: int = 0, prefix_block: int = 16,
                 kv_layout: str = "paged", paged_blocks: int = 0,
                 paged_block: int = 0, paged_fused: bool = True,
                 ledger=None):
        if kv_layout != "paged":
            raise ValueError("PagedSlotKVCache is the kv_layout='paged' "
                             "implementation")
        self._ledger = ledger
        if slots < 1:
            raise ValueError(f"slots must be positive, got {slots}")
        if prefix_cache_blocks < 0:
            raise ValueError(f"prefix_cache_blocks must be >= 0, got "
                             f"{prefix_cache_blocks}")
        if prefix_block < 1:
            raise ValueError(f"prefix_block must be positive, got "
                             f"{prefix_block}")
        self.kv_layout = "paged"
        self.slots = int(slots)
        self.max_len = int(model.max_len)
        self.greedy = bool(greedy)
        self.temperature = float(temperature)
        self.prefill_bucket = int(prefill_bucket)
        self.mesh = mesh
        # ONE block granularity: aliasing a pooled prefix block into a
        # slot's table only works when the prefix pool and the physical
        # pool agree on the block size
        block = int(paged_block) if paged_block else int(prefix_block)
        if prefix_cache_blocks and paged_block \
                and int(paged_block) != int(prefix_block):
            raise ValueError(
                f"paged_block ({paged_block}) must equal prefix_block "
                f"({prefix_block}) when the prefix pool is on: pool hits "
                f"alias physical blocks by pointer")
        if block < 1:
            raise ValueError(f"paged_block must be positive, got {block}")
        if self.max_len % block:
            raise ValueError(
                f"paged_block={block} must divide max_len={self.max_len}")
        self.paged_block = block
        self.prefix_block = block
        self.max_blocks = self.max_len // block          # table width
        # default pool: every slot can grow to max_len (+1 block CoW
        # headroom per slot when aliasing is possible) and the prefix
        # pool can pin its full capacity — sized so the default NEVER
        # exhausts; smaller explicit pools rely on can_admit deferral
        cow_pad = 1 if prefix_cache_blocks else 0
        self.num_blocks = int(paged_blocks) if paged_blocks else (
            self.slots * (self.max_blocks + cow_pad)
            + int(prefix_cache_blocks))
        if self.num_blocks < self.max_blocks + cow_pad:
            raise ValueError(
                f"paged_blocks={self.num_blocks} cannot hold even one "
                f"full slot ({self.max_blocks} blocks + {cow_pad} CoW "
                f"headroom)")
        self._scratch = self.num_blocks  # physical id of the scratch block

        self.quantized = False
        if kv_dtype is not None:
            kv_dtype = jnp.dtype(kv_dtype)
            self.quantized = kv_dtype == jnp.dtype(jnp.int8)
        keep_tp = (mesh is not None
                   and getattr(model, "partition_model", False)
                   and meshlib.MODEL_AXIS in mesh.axis_names)
        self.resumable_step = model.resumable_step
        self.state_leaves = dict(getattr(model, "slot_state", {}))
        self.ring_leaves = dict(getattr(model, "slot_rings", {}))
        self.served_model = type(model).__name__    # named in a refusal
        self.prefill_form = "scan"      # insert goes through _chunk
        self._scan_model_only("the paged layout")
        # fused clone for the decode/verify hot ops, gather clone for the
        # prefill scan (bitwise-monolithic math) — same params, same
        # cache variables, only the read path differs
        self.paged_fused = bool(paged_fused)
        self.dm = model.slot_decode_clone(
            partition_model=keep_tp, kv_quant=self.quantized,
            paged_blocks=self.num_blocks + 1, paged_block=block,
            paged_fused=self.paged_fused, paged_mesh=mesh)
        self.dm_gather = self.dm.clone(paged_fused=False)
        self._rng = rng if rng is not None else jax.random.key(0)

        dummy = jnp.zeros((self.slots, 1), jnp.int32)
        shapes = jax.eval_shape(
            lambda: self.dm.init(jax.random.key(0), dummy, train=False,
                                 positions=dummy))["cache"]
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        if kv_dtype is not None and not self.quantized:
            cache = jax.tree.map(
                lambda t: t.astype(kv_dtype)
                if jnp.issubdtype(t.dtype, jnp.floating) else t, cache)
        self.kv_dtype = "int8" if self.quantized else next(
            (str(leaf.dtype) for leaf in jax.tree.leaves(cache)
             if jnp.issubdtype(leaf.dtype, jnp.floating)), "float32")

        self._vec_sharding = None
        self._blk_sharding = None
        if mesh is not None:
            dp = mesh.shape.get(meshlib.DATA_AXIS, 1)
            if self.slots % dp:
                raise ValueError(
                    f"slots ({self.slots}) must divide by the mesh's data "
                    f"axis ({dp}): each data shard owns a contiguous slot "
                    f"block")
            # pool leaves REPLICATE: any slot (sharded over 'data') may
            # read/write any physical block, so a block-dim sharding
            # would turn every table-indirect access into a reshard
            repl = NamedSharding(mesh, P())
            cache = jax.tree.map(lambda t: jax.device_put(t, repl), cache)
            self._vec_sharding = meshlib.kv_slot_sharding(mesh, 1)
            self._blk_sharding = meshlib.kv_slot_sharding(mesh, 2)
        self.cache = cache

        # host slot table (identical to monolithic) ...
        self.lengths = np.zeros(self.slots, np.int32)
        self.active = np.zeros(self.slots, np.bool_)
        self.reserved = np.zeros(self.slots, np.bool_)
        self.tokens = np.zeros(self.slots, np.int32)
        self._pending: dict[int, dict] = {}
        self._init_multi_state()
        self.params = self._place_params(params)    # under self.tracer

        # ... plus the paged substrate: refcounted physical blocks, a
        # free list, per-slot logical→physical tables (host numpy; the
        # device sees a masked snapshot per program call)
        self._block_refs = np.zeros(self.num_blocks, np.int32)
        self._free_list = list(range(self.num_blocks))[::-1]  # pop() → 0,1,..
        self._slot_blocks: list[list[int]] = [[] for _ in range(self.slots)]
        self.block_tables_np = np.full(
            (self.slots, self.max_blocks), self._scratch, np.int32)
        # committed block budgets (can_admit's outstanding ledger):
        # worst-case blocks each live admission may still allocate
        self._slot_need = np.zeros(self.slots, np.int32)
        self._paged_counters = {"zero_copy_hits": 0, "zero_copy_blocks": 0,
                                "zero_copy_tokens": 0, "cow_copies": 0}

        # prefix pool: key → PHYSICAL BLOCK ID with a refcount pin (the
        # monolithic pool stores device byte copies; here the pool IS
        # the aliasing table — zero bytes stored twice)
        self.prefix_cache_blocks = int(prefix_cache_blocks)
        self._prefix_pool: OrderedDict[bytes, int] = OrderedDict()
        self.prefix_stats = {"hits": 0, "misses": 0, "evictions": 0,
                             "tokens_reused": 0, "inserted_blocks": 0}

        self.prefill_tokens_computed = 0
        self.prefill_tokens_padded = 0
        self._phase_s = {"prefill_s": 0.0, "decode_s": 0.0}

        self._step = self._build_step()
        self._prefills: dict[int, object] = {}   # unused: paged admission
        self._chunks: dict[int, object] = {}     # always chunks
        self._verifies: dict[int, object] = {}
        self._read_block = None                  # monolithic pool programs
        self._write_block = None                 # never built under paged
        self._copy_block = None                  # CoW block copy (lazy)
        self._handoff_read = None                # disagg KV handoff (lazy)
        self._handoff_write = None

    # -------------------------------------------------- block bookkeeping
    @property
    def blocks_in_use(self) -> int:
        """Allocated physical blocks (scratch excluded)."""
        return self.num_blocks - len(self._free_list)

    def _alloc_block(self) -> int:
        if not self._free_list:
            raise BlockPoolExhausted(
                f"paged KV pool exhausted: all {self.num_blocks} blocks "
                f"in use — the scheduler's can_admit gate should have "
                f"deferred this admission (block budget accounting bug, "
                f"or the pool was sized below slots × max_len/block)")
        bid = self._free_list.pop()
        self._block_refs[bid] = 1
        return bid

    def _release_block(self, bid: int) -> None:
        self._block_refs[bid] -= 1
        if self._block_refs[bid] == 0:
            self._free_list.append(bid)

    def _release_slot_blocks(self, slot: int) -> None:
        for bid in self._slot_blocks[slot]:
            self._release_block(bid)
        self._slot_blocks[slot].clear()
        self.block_tables_np[slot, :] = self._scratch
        self._slot_need[slot] = 0

    def _build_copy(self):
        def copy(cache, src, dst):
            return jax.tree.map(
                lambda t: lax.dynamic_update_slice(
                    t, lax.dynamic_slice(
                        t, (src,) + (0,) * (t.ndim - 1),
                        (1,) + t.shape[1:]),
                    (dst,) + (0,) * (t.ndim - 1)), cache)

        return self._jit(copy, "kv_paged_cow_copy", donate_argnums=0)

    def _ensure_writable(self, slot: int, start: int, end: int) -> None:
        """Make positions ``[start, end)`` of ``slot`` safely writable:
        allocate missing blocks, copy-on-write shared ones.  A shared
        block (refcount > 1 — aliased from the prefix pool or pinned BY
        it) gets one jitted block copy into a fresh allocation; the
        slot's table then points at its private copy and every other
        sharer keeps reading the original."""
        if end <= start:
            return
        sb = self._slot_blocks[slot]
        blk = self.paged_block
        last = min((end - 1) // blk, self.max_blocks - 1)
        for j in range(start // blk, last + 1):
            while len(sb) <= j:      # extend coverage with fresh blocks
                bid = self._alloc_block()
                sb.append(bid)
                self.block_tables_np[slot, len(sb) - 1] = bid
            bid = sb[j]
            if self._block_refs[bid] > 1:   # shared → copy-on-write
                if self._copy_block is None:
                    self._copy_block = self._build_copy()
                new = self._alloc_block()
                self.cache = self._copy_block(
                    self.cache, jnp.int32(bid), jnp.int32(new))
                self._release_block(bid)
                sb[j] = new
                self.block_tables_np[slot, j] = new
                self._paged_counters["cow_copies"] += 1

    def _masked_bt(self, mask):
        """Device block-table snapshot with non-participating rows routed
        wholly to scratch — their garbage scatter writes can never land
        in a live (possibly shared) block.  Value-cached like the slot
        vectors: an unchanged table re-uploads nothing."""
        bt = np.where(np.asarray(mask, np.bool_)[:, None],
                      self.block_tables_np, np.int32(self._scratch))
        return self._dev_cached("bt", bt.astype(np.int32),
                                put=self._put_repl)

    # ------------------------------------------------- admission budgets
    def _block_need(self, total_len: int) -> int:
        need = -(-int(total_len) // self.paged_block)
        if self.prefix_cache_blocks:
            need += 1   # CoW headroom: a fully-aligned prefix hit
                        # recomputes its last token INTO a shared block
        return min(need, self.max_blocks + (1 if self.prefix_cache_blocks
                                            else 0))

    def can_admit(self, prompt_len: int, max_new_tokens: int) -> bool:
        """Block-exhaustion admission gate: free blocks (minus what live
        admissions may still claim under their registered budgets) must
        cover this request's worst-case need.  Conservative — aliasing
        only helps (aliased blocks never touch the free list)."""
        outstanding = sum(
            max(int(self._slot_need[s]) - len(self._slot_blocks[s]), 0)
            for s in range(self.slots) if self._slot_need[s])
        need = self._block_need(int(prompt_len) + int(max_new_tokens))
        return len(self._free_list) - outstanding >= need

    def note_admission(self, slot: int, total_len: int) -> None:
        """Register an admitted request's worst-case block budget (the
        scheduler calls this with prompt + max_new_tokens); cleared on
        evict/abort."""
        self._slot_need[slot] = self._block_need(total_len)

    # ------------------------------------------------------------ programs
    def _build_step(self):
        dm = self.dm

        def step(params, cache, tokens, lengths, active, bt, rng):
            logits, upd = dm.apply(
                {"params": params, "cache": cache}, tokens[:, None],
                train=False, positions=lengths[:, None],
                block_tables=bt, mutable=["cache"])
            nxt = self._sample(logits[:, -1], rng).astype(tokens.dtype)
            return (upd["cache"], jnp.where(active, nxt, tokens),
                    jnp.where(active, lengths + 1, lengths))

        return self._jit(step, "kv_paged_decode_step", donate_argnums=1)

    def _chunk(self, lpad: int):
        """Chunk-resumable prefill over the FULL pool (there is no
        per-slot cache slice to extract — the slot's identity is its
        block table row): same scan/positions/sampling contract as the
        monolithic ``_chunk``, gather read path (bitwise-monolithic
        math over the gathered table)."""
        dm = self.dm_gather

        def chunk(params, cache, bt_row, tokens, start, n_valid, rng):
            def body(c, xs):
                tok, t = xs
                logits, upd = dm.apply(
                    {"params": params, "cache": c}, tok[None, None],
                    train=False, positions=t[None, None],
                    block_tables=bt_row, mutable=["cache"])
                return upd["cache"], logits[0, -1]

            cache, all_logits = lax.scan(
                body, cache,
                (tokens, start + jnp.arange(lpad, dtype=jnp.int32)))
            last = jnp.take(all_logits, n_valid - 1, axis=0)
            first = self._sample(last[None, :], rng)[0]
            return cache, first.astype(tokens.dtype)

        return self._jit(chunk, f"kv_paged_prefill_chunk_l{lpad}",
                         donate_argnums=1)

    def _verify(self, width: int):
        dm = self.dm

        def verify(params, cache, block, lengths, bt):
            positions = (lengths[:, None]
                         + jnp.arange(width, dtype=jnp.int32)[None, :])
            logits, upd = dm.apply(
                {"params": params, "cache": cache}, block,
                train=False, positions=positions, block_tables=bt,
                mutable=["cache"])
            return upd["cache"], logits.argmax(-1).astype(block.dtype)

        return self._jit(verify, f"kv_paged_verify_w{width}",
                         donate_argnums=1)

    # ------------------------------------------------------------ slot API
    def insert(self, prompt, slot: int | None = None) -> tuple[int, int]:
        """Paged admission ALWAYS routes through the chunk-resumable
        program (begin_insert + one uncapped chunk): there is no
        slice-out monolithic prefill over a shared pool, and chunked
        admission is the path whose writes go through
        ``_ensure_writable`` (allocation + CoW)."""
        slot, _ = self.begin_insert(prompt, slot)
        try:
            first = self.prefill_chunk(slot)
        except BaseException:
            if self.has_pending(slot):
                self.abort_insert(slot)
            elif self.active[slot]:
                self.evict(slot)
            raise
        assert first is not None
        return slot, first

    def prefill_chunk(self, slot: int,
                      max_tokens: int | None = None) -> int | None:
        pend = self._pending.get(slot)
        if pend is None:
            raise RuntimeError(f"slot {slot} has no pending admission "
                               f"(begin_insert first)")
        filled, lp = pend["filled"], pend["lp"]
        n = lp - filled
        if max_tokens is not None:
            if max_tokens < 1:
                raise ValueError(
                    f"max_tokens must be positive, got {max_tokens}")
            n = min(n, int(max_tokens))
        final = filled + n == lp
        # allocation + CoW BEFORE the program runs: the scan's writes
        # must only ever land in private (or scratch) blocks
        self._ensure_writable(slot, filled, filled + n)
        lpad = _bucket(n, 1, self.max_len)
        padded = np.zeros(lpad, np.int32)
        padded[:n] = pend["prompt"][filled:filled + n]
        if lpad not in self._chunks:
            self._chunks[lpad] = self._chunk(lpad)
        bt_row = self._put_repl(
            self.block_tables_np[slot:slot + 1].astype(np.int32))
        t0 = time.perf_counter()
        self.cache, first = self._chunks[lpad](
            self.params, self.cache, bt_row,
            self._put_repl(padded), jnp.int32(filled), jnp.int32(n),
            self._next_rng())
        if final:
            first = int(first)   # inside the timed region, as above
        self._phase_s["prefill_s"] += time.perf_counter() - t0
        pend["filled"] = filled + n
        self.lengths[slot] = filled + n
        self.prefill_tokens_computed += n
        self.prefill_tokens_padded += lpad
        if not final:
            return None
        del self._pending[slot]
        self.reserved[slot] = False
        self.active[slot] = True
        self.lengths[slot] = lp
        self.tokens[slot] = first
        self._pool_prefix(pend["prompt"], lp, slot)
        return first

    def abort_insert(self, slot: int) -> None:
        super().abort_insert(slot)
        self._release_slot_blocks(slot)

    def evict(self, slot: int) -> None:
        if not self.active[slot]:
            raise RuntimeError(f"slot {slot} is not active")
        self._release_slot_blocks(slot)
        self.active[slot] = False
        self.lengths[slot] = 0
        self.tokens[slot] = 0
        self._reset_multi_slot(slot)

    # ------------------------------------------------------- KV handoff
    def _handoff_block(self) -> int:
        """Paged handoff granularity IS the physical block: the transfer
        format serializes whole pool blocks by id, so block size and
        table block size agree by construction."""
        return self.paged_block

    def _handoff_ops(self):
        """Physical-block handoff programs (``_build_copy``'s slicing
        aimed across tables instead of within one): ``read`` slices one
        physical block out of every pool leaf, ``write`` scatters a
        transferred block into a freshly-allocated block of the
        receiving pool.  Block ids are traced — one compile each."""
        def read(cache, bid):
            return jax.tree.map(
                lambda t: lax.dynamic_slice(
                    t, (bid,) + (0,) * (t.ndim - 1),
                    (1,) + t.shape[1:]), cache)

        def write(cache, entry, bid):
            return jax.tree.map(
                lambda t, e: lax.dynamic_update_slice(
                    t, e.astype(t.dtype), (bid,) + (0,) * (t.ndim - 1)),
                cache, entry)

        return (self._jit(read, "kv_handoff_read_block"),
                self._jit(write, "kv_handoff_write_block",
                          donate_argnums=0))

    def extract_handoff(self, slot: int) -> dict:
        """Paged extract: serialize the physical blocks backing the
        slot's first ``ceil(length/block)`` table entries (aliased
        prefix blocks serialize like private ones — the payload is
        self-contained, the receiving pool shares nothing with ours)."""
        if not self.active[slot]:
            raise RuntimeError(f"slot {slot} is not active")
        if self._handoff_read is None:
            self._handoff_read, self._handoff_write = self._handoff_ops()
        length = int(self.lengths[slot])
        blk = self.paged_block
        n = -(-length // blk)
        sb = self._slot_blocks[slot]
        if len(sb) < n:
            raise RuntimeError(
                f"slot {slot} block table covers {len(sb)} blocks but "
                f"length {length} needs {n} — block bookkeeping bug")
        blocks = []
        for bid in sb[:n]:
            entry = self._handoff_read(self.cache, jnp.int32(bid))
            blocks.append(jax.device_get(entry))
        return {"layout": "paged", "block": blk, "length": length,
                "token": int(self.tokens[slot]),
                "kv_dtype": self.kv_dtype, "max_len": self.max_len,
                "blocks": blocks}

    def restore_handoff(self, payload: dict,
                        slot: int | None = None) -> tuple[int, int]:
        """Paged restore: allocate the covering blocks, scatter the
        payload in, point the slot's table at them.  Failure anywhere —
        pool exhausted mid-allocation, a device error mid-write —
        releases every block this restore claimed before re-raising, so
        a failed handoff admission cannot leak pool blocks."""
        blk = self.paged_block
        length = self._check_handoff_payload(payload, blk)
        n = -(-length // blk)
        if len(payload["blocks"]) != n:
            raise ValueError(
                f"handoff payload carries {len(payload['blocks'])} blocks "
                f"but length {length} needs {n}")
        slot = self._claim_restore_slot(length, slot)
        if self._handoff_write is None:
            self._handoff_read, self._handoff_write = self._handoff_ops()
        sb = self._slot_blocks[slot]
        try:
            for j, entry in enumerate(payload["blocks"]):
                bid = self._alloc_block()
                sb.append(bid)
                self.block_tables_np[slot, j] = bid
                entry = jax.tree.map(self._put_repl, entry)
                self.cache = self._handoff_write(
                    self.cache, entry, jnp.int32(bid))
        except BaseException:
            # slot is still inactive — releasing its blocks restores the
            # pool exactly (refcounts were 1: nothing aliased a block
            # that never finished arriving)
            self._release_slot_blocks(slot)
            raise
        self.active[slot] = True
        self.lengths[slot] = length
        self.tokens[slot] = token = int(payload["token"])
        return slot, token

    # ------------------------------------------------------- prefix pool
    def _restore_prefix(self, prompt: np.ndarray, lp: int,
                        slot: int) -> int:
        """The zero-copy hit: matched pool blocks are aliased into the
        slot's block table (pointer writes + refcount bumps) — no device
        traffic at all.  Reuse covers FULL blocks including the one
        holding the prompt's final token (unlike the monolithic
        ``(lp-1)//blk`` cap): the final token is still recomputed (reuse
        is capped at ``lp - 1`` positions), and its write into the
        shared last block is what exercises copy-on-write."""
        if not self.prefix_cache_blocks:
            return 0
        blk = self.prefix_block
        usable = lp // blk
        keys = self._prefix_keys(prompt, usable)
        matched = 0
        for key in keys:
            if key not in self._prefix_pool:
                break
            matched += 1
        reused = min(matched * blk, lp - 1)
        self.prefix_stats["hits"] += matched
        self.prefix_stats["misses"] += usable - matched
        self.prefix_stats["tokens_reused"] += reused
        if not matched:
            return 0
        sb = self._slot_blocks[slot]
        for b, key in enumerate(keys[:matched]):
            self._prefix_pool.move_to_end(key)   # LRU touch
            bid = self._prefix_pool[key]
            self._block_refs[bid] += 1
            sb.append(bid)
            self.block_tables_np[slot, b] = bid
        self._paged_counters["zero_copy_hits"] += 1
        self._paged_counters["zero_copy_blocks"] += matched
        self._paged_counters["zero_copy_tokens"] += reused
        return reused

    def _pool_prefix(self, prompt: np.ndarray, lp: int, slot: int) -> None:
        """Pool = pin: every full prompt block not already pooled gets a
        refcount pin on the slot's OWN physical block (no extraction, no
        copy — the pool and the slot share the block until eviction
        drops the slot's reference)."""
        if not self.prefix_cache_blocks:
            return
        blk = self.prefix_block
        sb = self._slot_blocks[slot]
        for b, key in enumerate(self._prefix_keys(prompt, lp // blk)):
            if key in self._prefix_pool:
                self._prefix_pool.move_to_end(key)
                continue
            bid = sb[b]
            self._block_refs[bid] += 1          # the pool's pin
            self._prefix_pool[key] = bid
            self.prefix_stats["inserted_blocks"] += 1
        while len(self._prefix_pool) > self.prefix_cache_blocks:
            _, bid = self._prefix_pool.popitem(last=False)
            self._release_block(bid)
            self.prefix_stats["evictions"] += 1

    def reset_prefix_cache(self) -> None:
        while self._prefix_pool:
            _, bid = self._prefix_pool.popitem(last=False)
            self._release_block(bid)
        for k in self.prefix_stats:
            self.prefix_stats[k] = 0
        for k in self._paged_counters:
            self._paged_counters[k] = 0

    # ------------------------------------------------------------- decode
    def advance(self, only=None) -> np.ndarray:
        mask = self.active if only is None else np.asarray(only, np.bool_)
        live = self.lengths[mask]
        if live.size and int(live.max()) >= self.max_len:
            raise SlotOverflow(
                f"active slot at length {int(live.max())} would write past "
                f"max_len={self.max_len}; the scheduler must bound "
                f"prompt + max_new_tokens at admission")
        if self._multi_pending:
            raise RuntimeError(
                "a fused multi-step round is in flight — drain it before "
                "a single-step advance (host mirrors lag the device)")
        for slot in np.flatnonzero(mask):
            pos = int(self.lengths[slot])
            self._ensure_writable(int(slot), pos, pos + 1)
        t0 = time.perf_counter()
        with self.tracer.span("step_dispatch"):
            self.cache, d_nxt, d_len = self._step(
                self.params, self.cache,
                self._dev_cached("tokens", self.tokens),
                self._dev_cached("lengths", self.lengths),
                self._dev_cached("mask", mask), self._masked_bt(mask),
                self._next_rng())
        with self.tracer.span("token_fetch"):
            nxt = np.asarray(d_nxt)
        self._phase_s["decode_s"] += time.perf_counter() - t0
        self.lengths[mask] += 1
        self.tokens = nxt.astype(np.int32)
        self._dev_learn("tokens", self.tokens, d_nxt)
        self._dev_learn("lengths", self.lengths, d_len)
        return nxt

    # ------------------------------------------------- multi-step decode
    def _multi(self, k: int):
        """Paged fused k-iteration decode: the monolithic scan with the
        masked block-table operand threaded through every step.  The
        table is a DISPATCH-TIME snapshot: `_multi_prepare` pre-extends
        each slot's coverage for all in-flight growth, and a slot the
        device deactivates keeps scattering at its frozen length — into
        its own covered block (overwritten before any read: validity is
        length-driven) or past the snapshot's coverage, which routes to
        the scratch block."""
        dm = self.dm
        max_len = self.max_len

        def multi(params, cache, d_tok, d_len, d_act, d_bud,
                  h_tok, h_len, h_act, h_bud, edited, eos, bt, rngs):
            tokens = jnp.where(edited, h_tok, d_tok)
            lengths = jnp.where(edited, h_len, d_len)
            active = jnp.where(edited, h_act, d_act)
            budget = jnp.where(edited, h_bud, d_bud)

            def body(carry, rng):
                cache, tokens, lengths, active, budget = carry
                logits, upd = dm.apply(
                    {"params": params, "cache": cache}, tokens[:, None],
                    train=False, positions=lengths[:, None],
                    block_tables=bt, mutable=["cache"])
                nxt = self._sample(logits[:, -1],
                                   rng).astype(tokens.dtype)
                nxt = jnp.where(active, nxt, tokens)
                nlen = jnp.where(active, lengths + 1, lengths)
                nbud = jnp.where(active & (budget > 0),
                                 budget - 1, budget)
                done = active & ((nxt == eos)
                                 | ((budget > 0) & (nbud <= 0))
                                 | (nlen >= max_len))
                return ((upd["cache"], nxt, nlen, active & ~done, nbud),
                        (nxt, active, done))

            carry, (toks, acts, dones) = lax.scan(
                body, (cache, tokens, lengths, active, budget), rngs)
            return carry, toks, acts, dones

        return self._jit(multi, f"kv_paged_decode_multi_k{k}",
                         donate_argnums=1)

    def _multi_prepare(self, mask: np.ndarray, k: int) -> tuple:
        """Cover every masked slot's worst-case in-flight growth —
        already-dispatched undrained rounds (``_inflight``) plus this
        round's k — so no fused write can land outside the slot's own
        blocks, then snapshot the masked block table as the program's
        extra operand."""
        for slot in np.flatnonzero(mask):
            start = int(self.lengths[slot])
            end = min(start + int(self._inflight[slot]) + k, self.max_len)
            self._ensure_writable(int(slot), start, end)
        return (self._masked_bt(mask),)

    def verify_block(self, block) -> np.ndarray:
        if not self.greedy:
            raise ValueError(
                "verify_block requires greedy sampling: the exact "
                "acceptance rule (accept while draft == target argmax) "
                "only exists for greedy decode")
        block = np.asarray(block, np.int32)
        if block.ndim != 2 or block.shape[0] != self.slots:
            raise ValueError(
                f"block must be (slots, width) = ({self.slots}, k+1), "
                f"got {block.shape}")
        width = int(block.shape[1])
        live = self.lengths[self.active]
        if live.size and int(live.max()) + width > self.max_len:
            raise SlotOverflow(
                f"verify width {width} at length {int(live.max())} would "
                f"write past max_len={self.max_len}; the scheduler must "
                f"cap the draft k by remaining slot capacity")
        for slot in np.flatnonzero(self.active):
            pos = int(self.lengths[slot])
            self._ensure_writable(int(slot), pos, pos + width)
        if width not in self._verifies:
            self._verifies[width] = self._verify(width)
        blk = jnp.asarray(block)
        if self._blk_sharding is not None:
            blk = jax.device_put(blk, self._blk_sharding)
        t0 = time.perf_counter()
        self.cache, g = self._verifies[width](
            self.params, self.cache, blk,
            self._dev_cached("lengths", self.lengths),
            self._masked_bt(self.active))
        g = np.asarray(g).astype(np.int32)
        self._phase_s["decode_s"] += time.perf_counter() - t0
        return g

    # --------------------------------------------------------- accounting
    def kv_bytes_per_slot(self) -> int:
        """HONEST paged capacity (the BASELINE stored-bytes rule): bytes
        actually backing live sequences — allocated pool blocks (K/V
        payload + int8 scales) plus the block tables — amortized over
        live (active or reserved) slots.  With nothing live this is the
        pool-warmth floor: whatever the prefix pool still pins, plus the
        tables.  The monolithic ``slots × max_len`` formula would claim
        capacity the pool never allocated."""
        per_block = sum(
            (int(leaf.size) // leaf.shape[0])
            * jnp.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(self.cache))
        table_bytes = self.block_tables_np.nbytes
        live = int(self.active.sum()) + int(self.reserved.sum())
        return (self.blocks_in_use * per_block
                + table_bytes) // max(live, 1)

    def paged_stats(self) -> dict:
        """Pool utilization + the zero-copy/CoW ledger (cumulative; the
        scheduler reads counter deltas per run)."""
        return {"num_blocks": self.num_blocks,
                "block": self.paged_block,
                "blocks_in_use": self.blocks_in_use,
                "utilization": self.blocks_in_use / self.num_blocks,
                **dict(self._paged_counters)}

    def compiled_programs(self) -> dict[str, int]:
        """Paged program inventory: ONE decode step, one chunk program
        per bucket (admission always chunks — there is no monolithic
        slice-out prefill over a shared pool), no prefix block-copy
        programs (hits are pointer writes), one verify program per
        width, plus at most one CoW block copy."""
        out = super().compiled_programs()
        out["paged_block_copies"] = 0 if self._copy_block is None else 1
        return out

    def timeline_gauges(self) -> dict[str, float]:
        """Paged gauge snapshot: the base slot-table gauges plus pool
        occupancy/refcounts, all host numpy — no device syncs.  Under
        paging ``kv_live_bytes`` is block-backed: allocated blocks ×
        stored bytes per block (aliased blocks counted once, exactly the
        zero-copy saving the pool exists for)."""
        per_block = getattr(self, "_tl_block_bytes", None)
        if per_block is None:
            per_block = self._tl_block_bytes = sum(
                (int(leaf.size) // leaf.shape[0])
                * jnp.dtype(leaf.dtype).itemsize
                for leaf in jax.tree.leaves(self.cache))
        live_tokens = int(self.lengths.sum())
        return {
            "kv_active_slots": int(self.active.sum()),
            "kv_reserved_slots": int(self.reserved.sum()),
            "kv_live_tokens": live_tokens,
            "kv_live_bytes": self.blocks_in_use * per_block,
            "kv_prefix_pool_blocks": len(self._prefix_pool),
            "kv_blocks_in_use": self.blocks_in_use,
            "kv_pool_refcount_total": int(self._block_refs.sum()),
            "kv_free_blocks": len(self._free_list),
        }
