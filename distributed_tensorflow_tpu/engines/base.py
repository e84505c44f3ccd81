"""Shared engine machinery: TrainState, loss, eval, batch placement.

Design: every engine is a single jitted SPMD program over a Mesh.  There is
no server process and no wire — where the reference moves pickled gradients
and weights over TCP every batch (reference client.py:85-90,
server.py:86-107), we move nothing off-device: XLA collectives combine
gradients/parameters across the mesh's ``data`` axis in-graph.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.parallel import collectives as coll
from distributed_tensorflow_tpu.parallel import compression
from distributed_tensorflow_tpu.parallel import mesh as meshlib
from distributed_tensorflow_tpu.parallel import overlap
from distributed_tensorflow_tpu.parallel import precision as precisionlib

PyTree = Any


@struct.dataclass
class TrainState:
    """Replaces the reference server's (model, optimizer) pair
    (reference server.py:148-155) as a pure value."""

    step: jax.Array
    params: PyTree
    opt_state: PyTree
    rng: jax.Array


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Sparse categorical crossentropy from logits — parity with the
    reference's loss (reference server.py:13-15, client.py:11-13)."""
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels)


def cross_entropy_onehot(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Cross-entropy via the one-hot contraction instead of a label gather.

    Same math as :func:`cross_entropy`; exists because XLA's SPMD
    partitioner CHECK-crashes (spmd_partitioner_util.cc device-group check)
    partitioning the take-along-axis GATHER over vocab-sharded logits inside
    a partial-manual shard_map region (composite engine + Megatron-TP GPT,
    whose tied head keeps logits vocab-sharded).  The one-hot form lowers to
    a reduction the partitioner handles; the extra FLOPs fuse into the loss
    reduction and are negligible next to the head matmul."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.sum(
        jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype) * logits,
        axis=-1)
    return lse - picked


def token_weights(mask: jax.Array, y: jax.Array) -> jax.Array:
    """Per-element eval weights: the pipeline yields one validity flag per
    ROW (B,), but LM labels are (B, L) per-token — broadcast the row mask
    over the label's trailing dims so `correct/loss/count` count tokens for
    LMs and examples for classifiers with one code path."""
    mask = mask.reshape(mask.shape + (1,) * (y.ndim - mask.ndim))
    return jnp.broadcast_to(mask, y.shape)


def make_loss_fn(apply_fn: Callable) -> Callable:
    def loss_fn(params, x, y, rng):
        logits = apply_fn({"params": params}, x, train=True, rngs={"dropout": rng})
        loss = cross_entropy(logits, y).mean()
        acc = (logits.argmax(-1) == y).mean()
        return loss, acc

    return loss_fn


def gspmd_grad_accum(grad_fn, params, x, y, rng, K: int, mesh=None,
                     batch_axes=meshlib.DATA_AXIS):
    """K-microbatch gradient accumulation under GSPMD (global jit
    semantics): reshape the batch to (K, B/K, ...), `lax.scan` the
    microbatches, accumulate gradients, divide by K once.

    ``grad_fn(params, xc, yc, rng_c) -> ((loss, aux), grads)`` — a
    ``value_and_grad(..., has_aux=True)`` of a per-chunk mean loss; ``aux``
    is any pytree of scalars, accumulated leaf-wise and K-averaged.  The
    returned gradient is then the global batch mean (mean of equal-chunk
    means), identical math to K=1 — the GSPMD counterpart of the sync
    engine's shard_map accumulation (engines/sync.py:68-111), but with no
    manual psum: 'data' stays a GSPMD axis, so each chunk's gradient is
    already globally reduced and the scan just sums K of them.  Activation
    memory drops ~K× (one microbatch's activations live at a time);
    gradient-accumulator memory is one extra param-sized buffer, sharded
    like the params themselves.

    Dropout draws an independent key per microbatch (fold_in on the chunk
    index), matching K separate steps.

    ``mesh``, when given, pins the microbatched inputs to
    ``P(None, batch_axes, ...)`` (K replicated, batch sharded —
    ``batch_axes`` defaults to 'data'; the expert engine passes its
    ('data','expert') combined batch axes).  Without the
    constraint the (B, ...) → (K, B/K, ...) reshape leaves the sharding
    of the new leading axis to propagation, and inside the scan body the
    partitioner can fail to move from its guess to what the embedding
    gather needs — an "Involuntary full rematerialization"
    (replicate-then-repartition) per microbatch on fsdp×tp BERT."""
    if x.shape[0] % K:
        raise ValueError(
            f"global batch {x.shape[0]} not divisible by grad_accum {K}")
    xm = x.reshape((K, x.shape[0] // K) + x.shape[1:])
    ym = y.reshape((K, y.shape[0] // K) + y.shape[1:])
    if mesh is not None:
        axes = batch_axes if isinstance(batch_axes, tuple) else (batch_axes,)
        n_batch = 1
        for a in axes:
            n_batch *= mesh.shape[a]
        # pin ONLY when each chunk's batch divides the batch-axes size:
        # forcing an uneven shard pads the per-device batch, and the padded
        # rows' embedding-gather cotangents scatter-add garbage into real
        # vocab rows (caught by test_tp_grad_accum_matches_k1 at K=4 on a
        # data=4 mesh — chunk batch 2).  When indivisible, sharding
        # propagation's own choice is left alone.
        if (x.shape[0] // K) % n_batch == 0:
            def pin(t):
                spec = P(None, batch_axes,
                         *([None] * (t.ndim - 2)))
                return jax.lax.with_sharding_constraint(
                    t, NamedSharding(mesh, spec))

            xm, ym = pin(xm), pin(ym)

    def micro(carry, chunk):
        g_acc, l_acc, a_acc, i = carry
        xc, yc = chunk
        (l, a), g = grad_fn(params, xc, yc, jax.random.fold_in(rng, i))
        return (jax.tree.map(jnp.add, g_acc, g),
                l_acc + l, jax.tree.map(jnp.add, a_acc, a), i + 1), None

    # aux may be any pytree of scalars (acc, or (task, acc, overflow) for
    # the MoE engine) — zeros come from an abstract eval, no FLOPs
    aux_shape = jax.eval_shape(
        lambda: grad_fn(params, xm[0], ym[0], rng)[0][1])
    aux_init = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), aux_shape)
    zero = jnp.zeros((), jnp.float32)
    init = (jax.tree.map(jnp.zeros_like, params), zero, aux_init,
            jnp.zeros((), jnp.int32))
    (g_sum, l_sum, a_sum, _), _ = jax.lax.scan(micro, init, (xm, ym))
    grads = jax.tree.map(lambda t: t / K, g_sum)
    return grads, l_sum / K, jax.tree.map(lambda t: t / K, a_sum)


def gspmd_value_and_grad(loss_fn, params, x, y, rng, K: int, mesh=None,
                         loss_scale=None):
    """(grads, loss, acc) of a GSPMD step — direct at K == 1, K-microbatch
    accumulated otherwise.  The shared step core of the jit engines
    (tensor_parallel, fsdp); ``loss_fn`` has the make_loss_fn signature.
    ``mesh`` pins microbatch shardings under accumulation (see
    gspmd_grad_accum).

    ``loss_scale`` is the GSPMD family's ONE loss-scaling hook
    (parallel/precision.py fp16-f32master): when given (a traced f32
    scalar read out of the step's opt_state), the DIFFERENTIATED value is
    ``loss × scale`` — fp16 backward intermediates stay in range — while
    the returned metric loss stays unscaled (it rides the aux);
    gradients come back SCALED and the master-weights wrapper unscales
    them.  ``None`` (every non-fp16 policy) compiles the exact unscaled
    program."""
    if loss_scale is not None:
        def scaled_fn(p, xc, yc, rng_c):
            loss, acc = loss_fn(p, xc, yc, rng_c)
            return loss * loss_scale, (loss, acc)

        grad_fn = jax.value_and_grad(scaled_fn, has_aux=True)
        if K == 1:
            (_, (loss, acc)), grads = grad_fn(params, x, y, rng)
            return grads, loss, acc
        grads, _scaled_sum, aux = gspmd_grad_accum(
            grad_fn, params, x, y, rng, K, mesh=mesh)
        loss, acc = aux
        return grads, loss, acc
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    if K == 1:
        (loss, acc), grads = grad_fn(params, x, y, rng)
        return grads, loss, acc
    return gspmd_grad_accum(grad_fn, params, x, y, rng, K, mesh=mesh)


class Engine:
    """Base: owns model, optimizer, mesh; subclasses build the step program."""

    axis = meshlib.DATA_AXIS
    # engines whose step threads the traced loss scale out of opt_state
    # into their loss (the fp16-f32master prerequisite) set this True; the
    # base constructor rejects a scaling policy on any engine that does
    # not — silently training UNscaled loss while the wrapper divides by
    # the scale would shrink the effective LR by the scale factor
    supports_loss_scaling = False

    def __init__(
        self,
        model,
        optimizer: optax.GradientTransformation | None = None,
        mesh=None,
        learning_rate: float = 1e-3,
        grad_compression: str | compression.GradCodec = "none",
        grad_bucket_mb: float = 0.0,
        precision: str | precisionlib.PrecisionPolicy = "f32",
    ):
        self.model = model
        self.tx = optimizer if optimizer is not None else optax.adam(learning_rate)
        self.mesh = mesh if mesh is not None else meshlib.create_mesh()
        self.n_devices = self.mesh.shape[self.axis]
        # mixed-precision policy (--precision; parallel/precision.py):
        # 'f32' (default) is a strict no-op — no cast, no wrap, the
        # compiled programs are byte-identical to the pre-policy ones.
        # Master policies wrap the optimizer HERE, before enable_health
        # chains its captures around the result, so health sees the raw
        # grads in and the final emitted updates out.
        self.precision = precisionlib.make_policy(precision)
        if self.precision.loss_scaling and not self.supports_loss_scaling:
            raise ValueError(
                f"precision '{self.precision.name}' needs dynamic loss "
                f"scaling, which {type(self).__name__} does not thread "
                f"into its loss — use a bf16 policy (bf16/bf16-f32master: "
                f"bfloat16 shares f32's exponent range, no scaling "
                f"needed), or train with a loss-scaling engine "
                f"(sync/allreduce/fsdp/tensor_parallel)")
        if self.precision.active:
            self.tx = self.precision.wrap_optimizer(self.tx)
        # cross-device gradient/parameter exchange codec (--grad-compression;
        # parallel/compression.py): 'none' compiles to the pre-codec program.
        # --grad-bucket-mb > 0 wraps it in the bucketed overlap codec
        # (parallel/overlap.py): size-targeted reverse-backward buckets
        # whose independent per-bucket collectives XLA's latency-hiding
        # scheduler can run behind the remaining backward compute; 0 (the
        # default) keeps the codec unwrapped — byte-identical programs.
        self.grad_codec = overlap.make_overlap_codec(grad_compression,
                                                     grad_bucket_mb)
        self._step_fn = None
        self._eval_fn = None
        self._many_step_fns: dict[int, Callable] = {}  # k → jitted scan drain
        self._init_shardings = None  # set by _init_partitioned_state
        # numeric-health layer (observability/health.py): None = off — no
        # optimizer wrap, no extra metrics, the compiled program is the
        # pre-health one.  enable_health() installs the capture transforms.
        self.health = None
        self._health_step_fn = None
        self._health_ema_val = None  # device (ema, count) loss-EMA carry
        self._precision_step_fn = None  # jitted scale-stats step (fp16)

    # ---------------------------------------------------------------- init
    def init_state(self, rng: jax.Array, sample_x: np.ndarray) -> TrainState:
        """Initialize replicated state (subclasses may re-layout).  The
        precision policy's storage cast happens HERE, before ``tx.init``:
        the optimizer (and a master policy's f32 copy) is built over the
        params the steps will actually train."""
        params = self.model.init(rng, jnp.asarray(sample_x[:1]), train=False)["params"]
        params = self.precision.cast_params(params)
        opt_state = self.tx.init(params)
        state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                           opt_state=opt_state, rng=rng)
        # every process computed the same state (same rng); state_to_global
        # makes it one global replicated array on multi-process meshes
        return meshlib.state_to_global(state, meshlib.replicated(self.mesh))

    # ------------------------------------------------------------- batches
    def _place(self, arr, sharding, process_local: bool):
        """One batch-array placement: full-host copy or process-local rows."""
        if process_local:
            return meshlib.local_to_global(arr, sharding)
        return meshlib.host_to_global(arr, sharding)

    def shard_batch(self, x: np.ndarray, y: np.ndarray,
                    mask: np.ndarray | None = None,
                    process_local: bool = False):
        """Place a batch with its leading dim split over the data axis.

        ``process_local=False``: every process passes the same global batch
        (one host batch feeds all devices).  ``process_local=True``: each
        process passes its OWN rows (global_batch / process_count of them)
        from its input shard — the multi-host rendering of the reference's
        per-worker dataset sharding (reference initializer.py:44).
        """
        xs = self._place(x, meshlib.data_sharding(self.mesh, x.ndim),
                         process_local)
        ys = self._place(y, meshlib.data_sharding(self.mesh, y.ndim),
                         process_local)
        if mask is None:
            return xs, ys
        ms = self._place(mask, meshlib.data_sharding(self.mesh, mask.ndim),
                         process_local)
        return xs, ys, ms

    # -------------------------------------------------------------- health
    def enable_health(self, config=None):
        """Turn on the numeric-health layer (``--health on``): wraps the
        optimizer with the capture transforms of observability/health.py,
        so every subsequent step's metrics additionally carry
        ``grad_norm / param_norm / update_norm / update_ratio /
        nonfinite_count / loss_spike`` — computed on device, stacked
        through the many-step scan like any other metric.

        Must run BEFORE ``init_state``/the first step: the optimizer state
        tree gains its capture slots at ``tx.init``.  With health off
        (never called) nothing here touches the engine — the compiled
        program stays bitwise identical to the pre-health one."""
        from distributed_tensorflow_tpu.observability import health as hl

        if self.health is not None:
            return self.health
        if (self._step_fn is not None or self._many_step_fns
                or self._init_shardings is not None):
            raise RuntimeError(
                "enable_health() must run before the engine builds its "
                "step program or initializes state (the optimizer tree "
                "gains capture slots at tx.init)")
        self.health = config if config is not None else hl.HealthConfig()
        self.tx = hl.wrap_optimizer(self.tx, self.health)
        return self.health

    def _health_ema(self):
        from distributed_tensorflow_tpu.observability import health as hl

        if self._health_ema_val is None:
            self._health_ema_val = hl.ema_init()
        return self._health_ema_val

    def _check_health_state(self, state) -> None:
        """A state initialized BEFORE enable_health() carries no capture
        slots (the replicated engines' init_state sets none of the fields
        the enable-time guard can see) — fail at first step with the
        actionable message instead of an opaque optax tree-structure
        mismatch deep inside the jit."""
        from distributed_tensorflow_tpu.observability import health as hl

        hl.from_opt_state(state.opt_state)

    def _health_wrap(self, step):
        """``(state, ema, x, y) -> (state, ema, metrics ∪ health)``: run
        the engine's step, read the captured health scalars back out of
        the NEW opt_state, and score the loss against its running EMA —
        all inside the jit, so the health trajectory stacks through the
        scan exactly like loss/accuracy (k-invariant, flushed per chunk)."""
        from distributed_tensorflow_tpu.observability import health as hl

        cfg = self.health

        def stepped(state, ema, x, y):
            new_state, metrics = step(state, x, y)
            stats = hl.from_opt_state(new_state.opt_state)
            if "loss_scale" in metrics:
                # fp16 loss scaling: the grad capture sits BEFORE the
                # master-weights unscale, so its norm carries the scale —
                # divide it back out so grad_norm stays comparable across
                # precision policies (nan/inf divide through unchanged,
                # the anomaly signal survives).  The ENTERING state's
                # scale is the one the gradients were multiplied by;
                # metrics["loss_scale"] is post-update and differs on
                # every grow/backoff step
                entering = precisionlib.loss_scale_from(state.opt_state)
                stats["grad_norm"] = stats["grad_norm"] / entering
            if "loss" in metrics:
                spike, ema = hl.ema_spike(metrics["loss"], ema, cfg)
                stats["loss_spike"] = spike
            return new_state, ema, {**metrics, **stats}

        return stepped

    # ----------------------------------------------------------- precision
    def _precision_wrap(self, step):
        """``(state, x, y) -> (state, metrics ∪ {loss_scale, ls_skipped})``
        — read the dynamic-loss-scale bookkeeping back out of the NEW
        opt_state inside the jit, so skip accounting stacks through the
        scan exactly like loss/accuracy (k-invariant).  Installed only
        when the policy scales; every other policy compiles the engine's
        untouched step."""

        def stepped(state, x, y):
            new_state, metrics = step(state, x, y)
            stats = precisionlib.scale_stats_from(new_state.opt_state)
            return new_state, {**metrics, **stats}

        return stepped

    def _base_step(self):
        """The engine's step with the precision metrics wrap applied when
        the policy scales — the single composition point ``step`` and
        ``build_many_step`` share (the health wrap then goes OUTSIDE, so
        its anomaly policy sees the scaling stats too)."""
        if self._step_fn is None:
            self._step_fn = self._build_step()
        if self.precision.loss_scaling:
            return self._precision_wrap(self._step_fn)
        return self._step_fn

    # ---------------------------------------------------------------- step
    def step(self, state: TrainState, x, y):
        base = self._base_step()
        if self.health is None:
            if not self.precision.loss_scaling:
                return base(state, x, y)
            if self._precision_step_fn is None:
                self._precision_step_fn = jax.jit(base, donate_argnums=0)
            return self._precision_step_fn(state, x, y)
        if self._health_step_fn is None:
            self._check_health_state(state)
            # the outer jit inlines the engine's jitted step; the state is
            # donated as before (the two-scalar EMA carry is not worth
            # donation bookkeeping)
            self._health_step_fn = jax.jit(
                self._health_wrap(base), donate_argnums=0)
        state, ema, metrics = self._health_step_fn(
            state, self._health_ema(), x, y)
        self._health_ema_val = ema
        return state, metrics

    def _build_step(self):
        raise NotImplementedError

    # ------------------------------------------------------ multi-step drain
    def build_many_step(self, k: int):
        """One jitted program that runs ``k`` training steps as a
        ``lax.scan`` over ``k`` pre-staged device batches.

        Signature: ``many(state, xs_k, ys_k) -> (state, metrics)`` where
        ``xs_k``/``ys_k`` are length-``k`` tuples of batches already placed
        with this engine's input sharding (``shard_batch``), and each
        ``metrics`` leaf comes back stacked ``(k,)`` — the per-step
        trajectory, materializable with ONE host sync per call.  The tuples
        are stacked on-device inside the jit (no host-side concat), then the
        scan slices them back per step, so each slice keeps the batch
        sharding it was placed with.

        This is the steady-state fast path of ``Trainer.fit``
        (``steps_per_call``): the per-step Python dispatch + host round-trip
        that made the single-step loop swing 0.87→1.68× with zero code
        changes (BASELINE.md methodology) happens once per *chunk* instead
        of once per step.  The scan body is the engine's own donated
        ``train_step`` — identical math step for step.

        With the health layer on (``enable_health``) the signature gains
        the loss-EMA carry — ``many(state, ema, xs_k, ys_k) -> (state,
        ema, metrics)`` — and each ``metrics`` leaf includes the stacked
        per-step health stats; ``many_step`` threads the carry, so callers
        going through it see no difference.  Health OFF compiles the exact
        pre-health program below, untouched.
        """
        if k < 1:
            raise ValueError(f"steps_per_call must be >= 1, got {k}")
        # loss-scaling policies ride the same wrap here as in step():
        # the per-step loss_scale/ls_skipped stats stack through the scan
        step = self._base_step()

        if self.health is None:
            def many(state, xs_k, ys_k):
                def body(st, batch):
                    x, y = batch
                    return step(st, x, y)

                return jax.lax.scan(body, state,
                                    (jnp.stack(xs_k), jnp.stack(ys_k)))

            return jax.jit(many, donate_argnums=0)

        hstep = self._health_wrap(step)

        def many_health(state, ema, xs_k, ys_k):
            def body(carry, batch):
                st, e = carry
                x, y = batch
                st, e, m = hstep(st, e, x, y)
                return (st, e), m

            (state, ema), metrics = jax.lax.scan(
                body, (state, ema), (jnp.stack(xs_k), jnp.stack(ys_k)))
            return state, ema, metrics

        # state donated as in the health-off drain; the two-scalar EMA
        # carry is not worth donation bookkeeping
        return jax.jit(many_health, donate_argnums=0)

    def many_step(self, state: TrainState, xs_seq, ys_seq):
        """Run ``len(xs_seq)`` steps through the cached scanned drain
        (``build_many_step``); one compiled program per distinct chunk
        length.  Engines with a host-side per-step overflow watch (the MoE
        engines' ``overflow_monitor``, fed per step by their ``step()``
        overrides) get it fed here too, one still-lazy slice per step of
        the stacked metric — same window cadence as the single-step path."""
        k = len(xs_seq)
        fn = self._many_step_fns.get(k)
        if fn is None:
            if self.health is not None:
                self._check_health_state(state)
            fn = self.build_many_step(k)
            self._many_step_fns[k] = fn
        if self.health is None:
            state, metrics = fn(state, tuple(xs_seq), tuple(ys_seq))
        else:
            state, ema, metrics = fn(state, self._health_ema(),
                                     tuple(xs_seq), tuple(ys_seq))
            self._health_ema_val = ema
        monitor = getattr(self, "overflow_monitor", None)
        if monitor is not None and "overflow" in metrics:
            for i in range(k):
                monitor.observe(metrics["overflow"][i])
        return state, metrics

    # ----------------------------------------------------------- spec map
    def state_partition_specs(self, state: TrainState) -> PyTree:
        """Per-leaf ``PartitionSpec`` tree of this engine's state layout —
        the spec map elastic resharding restores a checkpoint under
        (elastic/reshard.py): a leaf loaded from a checkpoint written on a
        DIFFERENT mesh shape is re-placed as ``NamedSharding(self.mesh,
        spec)`` of its entry here.  Derived from the live leaf shardings
        of ``state`` (typically a fresh ``init_state`` template), so every
        engine's layout — replicated, fsdp-sharded, tensor-parallel, and
        a precision policy's master copies inside ``opt_state`` — is
        covered by the one base implementation; leaves without a
        ``NamedSharding`` (host scalars) map to replicated ``P()``."""
        def spec_of(leaf):
            sh = getattr(leaf, "sharding", None)
            if isinstance(sh, NamedSharding):
                return sh.spec
            return P()

        return jax.tree.map(spec_of, state)

    # ----------------------------------------------------------- telemetry
    def grad_collective_bytes_raw(self, state: TrainState) -> int:
        """UNCOMPRESSED bytes one gradient collective round moves (the
        data-axis allreduce of sync DP), from the REAL param leaf dtypes —
        gradients share the params' shapes and dtypes, so for the
        replicated-param engines this is the per-step payload (the leaves'
        own itemsize, not an assumed 4 B/param).  Engines whose state layout or
        collective cadence differs override this (async/gossip stack a
        leading per-device axis and sync every ``sync_every`` steps).
        0 when the state carries no param pytree."""
        params = getattr(state, "params", None)
        if params is None:
            return 0
        try:
            return int(sum(np.prod(a.shape) * a.dtype.itemsize
                           for a in jax.tree.leaves(params)))
        except Exception:  # exotic leaf without shape/dtype
            return 0

    def grad_collective_bytes(self, state: TrainState) -> int:
        """Wire bytes of one gradient collective round under this engine's
        ``grad_compression`` codec (bf16 halves the raw figure, int8
        quarters it plus one f32 scale per leaf; 'none' equals
        ``grad_collective_bytes_raw``).  On the explicit-collective
        engines (sync/async/gossip) this is what actually crosses ICI;
        on the GSPMD engines the collective is compiler-inserted and the
        codec is a quantize→dequantize roundtrip, so this is the codec's
        payload ACCOUNTING, not the executed transfer
        (parallel/compression.py module docstring).  Telemetry (the
        tracer's ``collective_profile`` event, the fit result)
        reports BOTH figures so the compression win is visible."""
        params = getattr(state, "params", None)
        if params is None:
            return 0
        try:
            return self.grad_codec.wire_bytes(jax.tree.leaves(params))
        except Exception:  # exotic leaf without shape/dtype
            return 0

    def _bytes_per_device(self, tree) -> int:
        """Bytes of ``tree`` resident on ONE local device — real shard
        bytes for sharded leaves (FSDP/TP state counts its 1/n), full
        bytes for replicated/host leaves.  The first *addressable* device
        keeps the count real on every host of a multi-process mesh."""
        if tree is None:
            return 0
        dev = jax.local_devices()[0]
        total = 0
        for leaf in jax.tree.leaves(tree):
            shards = getattr(leaf, "addressable_shards", None)
            if shards is None:
                total += int(getattr(leaf, "nbytes", 0) or 0)
                continue
            for sh in shards:
                if sh.device == dev:
                    total += sh.data.nbytes
        return total

    def param_bytes_per_device(self, state: TrainState) -> int:
        """Per-device parameter bytes — THE storage number the precision
        policy halves (bf16 storage ≈ f32/2): reported in the fit result
        and run report, gated lower-is-better by ``analyze diff``."""
        return self._bytes_per_device(getattr(state, "params", None))

    def opt_state_bytes_per_device(self, state: TrainState) -> int:
        """Per-device optimizer-state bytes.  Master policies GROW this
        (the f32 master lives here — the documented trade of
        bf16-f32master); the pure ``bf16`` policy halves it."""
        return self._bytes_per_device(getattr(state, "opt_state", None))

    def roofline_model(self):
        """Analytic cost model of this engine's model for ``--roofline``
        MFU attribution (observability/roofline.py), or None for model
        families the analytic accounting doesn't cover (CNN/MLP/BERT —
        their MFU then honestly reports None rather than a GPT formula
        applied to the wrong architecture).  Engines that microbatch
        (composite/expert_parallel ``grad_accum``) need no override:
        model FLOPs per optimizer step are grad-accum invariant."""
        from distributed_tensorflow_tpu.observability.roofline import (
            GPTCostModel)

        return GPTCostModel.from_model(self.model)

    # ---------------------------------------------------------------- eval
    def eval_params(self, state: TrainState) -> PyTree:
        """Parameters to evaluate with (replicated). Subclasses with
        per-device parameter copies override to average first."""
        return state.params

    def _build_eval_gspmd(self, logits_fn):
        """Masked eval under plain jit (GSPMD semantics: params keep their
        shardings, XLA gathers per layer).  Shared by the engines whose
        params must not be re-replicated wholesale (fsdp, pipeline); the
        base shard_map eval below is for replicated-param engines."""

        def eval_step(params, x, y, mask):
            logits = logits_fn(params, x)
            w = token_weights(mask, y)
            correct = ((logits.argmax(-1) == y) * w).sum()
            loss_sum = (cross_entropy(logits, y) * w).sum()
            return correct, loss_sum, w.sum()

        return jax.jit(eval_step)

    def _build_eval(self):
        apply_fn = self.model.apply
        axis = self.axis

        def device_eval(params, x, y, mask):
            logits = apply_fn({"params": params}, x, train=False)
            w = token_weights(mask, y)
            correct = coll.all_reduce_sum(
                ((logits.argmax(-1) == y) * w).sum(), axis)
            loss_sum = coll.all_reduce_sum((cross_entropy(logits, y) * w).sum(), axis)
            count = coll.all_reduce_sum(w.sum(), axis)
            return correct, loss_sum, count

        smapped = jax.shard_map(
            device_eval, mesh=self.mesh,
            in_specs=(P(), P(self.axis), P(self.axis), P(self.axis)),
            out_specs=(P(), P(), P()),
        )
        return jax.jit(smapped)

    def evaluate(self, state: TrainState, dataset, batch_size: int = 100) -> dict:
        """Full-test-set eval — parity with the reference's server-side eval on
        the unsharded test set (reference server.py:24-37, 179-180), not the
        per-shard eval of dist_keras (reference dist_keras.py:53)."""
        if self._eval_fn is None:
            self._eval_fn = self._build_eval()
        params = self.eval_params(state)
        bs = max(batch_size, self.n_devices)
        bs = (bs // self.n_devices) * self.n_devices
        tot_correct = tot_loss = tot_count = 0.0
        for bx, by, bm in dataset.batches(bs, shuffle=False):
            xs, ys, ms = self.shard_batch(bx, by, bm)
            c, l, n = self._eval_fn(params, xs, ys, ms)
            tot_correct += float(c)
            tot_loss += float(l)
            tot_count += float(n)
        return {
            "accuracy": tot_correct / max(tot_count, 1.0),
            "loss": tot_loss / max(tot_count, 1.0),
            "count": int(tot_count),
        }

    # ------------------------------------------------------------- helpers
    def _per_device_rng(self, state_rng: jax.Array, step: jax.Array) -> jax.Array:
        rng = jax.random.fold_in(state_rng, step)
        return jax.random.fold_in(rng, coll.axis_index(self.axis))

    def _init_partitioned_state(self, rng: jax.Array, sample_x,
                                init_model=None,
                                spec_fn=None) -> TrainState:
        """Sharded init for GSPMD engines: abstract-eval the init to read
        the model's `with_partitioning` annotations, then jit-init with
        those shardings so large params materialize already sharded (never
        replicated-then-resharded).  Unannotated params replicate.

        ``spec_fn`` overrides the annotation-derived specs: it receives the
        UNBOXED abstract state tree AND the annotation-derived spec tree,
        and returns a matching tree of `PartitionSpec`s (the FSDP engine
        merges data-axis sharding into the annotations this way).  The
        resolved shardings are kept on ``self._init_shardings`` for engines
        that pin step outputs.

        The returned state is UNBOXED (plain arrays, no `nn.Partitioned`
        wrappers): the annotations' only runtime job is done once the arrays
        carry their NamedShardings, and boxed leaves break under
        partial-manual shard_map — flax re-applies each box's spec via
        with_sharding_constraint at apply time, which crashes on
        DenseGeneral's pre-reshape kernels (rank-2 value, rank-3 spec).

        ``init_model`` optionally substitutes a structurally-identical module
        for tracing init (e.g. a dense-attention twin when the engine's model
        needs in-shard_map collectives that can't trace here).
        """
        import flax.linen as nn
        from jax.sharding import NamedSharding

        x = jnp.asarray(sample_x[:1])
        module = init_model if init_model is not None else self.model

        def boxed_init(rng):
            params = module.init(rng, x, train=False)["params"]
            # storage cast INSIDE the traced init (no-op for f32): the
            # abstract eval below then derives shardings for the FINAL
            # dtypes — low-precision params materialize already sharded,
            # and a master policy's f32 copy (created by tx.init via
            # jax.tree.map, so nn.Partitioned boxes survive) inherits the
            # same partition annotations as the params it mirrors
            params = self.precision.cast_params(params)
            opt_state = self.tx.init(params)
            return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                              opt_state=opt_state, rng=rng)

        def init_fn(rng):
            return nn.unbox(boxed_init(rng))

        abstract = jax.eval_shape(boxed_init, rng)
        if spec_fn is None:
            specs = nn.get_partition_spec(abstract)
        else:
            specs = spec_fn(nn.unbox(abstract),
                            nn.get_partition_spec(abstract))
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda s: isinstance(s, P))
        self._init_shardings = shardings
        return jax.jit(init_fn, out_shardings=shardings)(rng)
