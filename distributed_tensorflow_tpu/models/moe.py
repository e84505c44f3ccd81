"""Mixture-of-Experts layers: a capacity-limited Switch/GShard layer and a
dropless top-k layer.

No reference counterpart (SURVEY.md §2.2: "EP (expert parallel): NO — no MoE
anywhere"); this is TPU-native new capability completing the parallelism
matrix (dp/tp/pp/sp/ep).

``MoELayer`` is the OLD layer, the GShard/Switch dense-dispatch
formulation that GSPMD partitions well for training under
``engines/expert_parallel.py``: top-1 or top-2 only, softmax gates, ReLU
experts, and a CAPACITY per expert past which tokens are dropped.

* Expert FFN weights are *stacked* with a leading expert dimension and
  annotated ``with_partitioning`` on the ``expert`` mesh axis — each device
  on that axis holds ``E / ep`` experts.
* Routing is expressed as two einsums against a dispatch tensor
  ``[tokens, E, capacity]`` (build: top-1 gate → capacity-limited position
  via cumsum).  Static shapes throughout — capacity is computed at trace
  time — so everything jits; under GSPMD the dispatch einsum lowers to the
  all-to-all that moves token slots to their expert's device over ICI.
* Router math (softmax, load-balance stats) runs in f32 regardless of the
  model compute dtype (routing decisions are precision-sensitive).

The Switch load-balancing auxiliary loss is sown into the
``intermediates`` collection as ``aux_loss``; the expert-parallel engine
adds ``aux_weight ×`` it to the task loss.

``DroplessMoE`` is the layer of today's sparse decoders
(models/mla_moe.py): any k, sigmoid scores with a choice bias, SwiGLU
experts, shared experts, NO capacity and no ``[tokens, E, capacity]``
tensor — the (token, choice) pairs are sorted by expert and go through one
grouped matrix product (``lax.ragged_dot``), so cost is linear in tokens
and no token is ever dropped.  It is told which experts it holds.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.parallel import mesh as meshlib


class MoELayer(nn.Module):
    """Capacity-limited Switch/GShard layer: top-k (k ∈ {1, 2}) routed
    expert FFN over tokens (leading axis of x); over capacity a token is
    DROPPED.  ``DroplessMoE`` below is the layer for more than two experts
    per token, sigmoid scores, shared experts or no drops.

    ``router_top_k=1`` is Switch routing; ``2`` is GShard-style top-2 with
    renormalized gates and priority positions (top-1 assignments claim
    capacity slots before any top-2 assignment).  The layer sows, per
    call:
      * ``aux_loss``  — Switch load-balance loss (token fraction × mean
        router prob, over top-1 choices);
      * ``z_loss``    — router logit z-loss, mean(logsumexp(logits)²)
        (stabilizes router logits; weighted by the engine);
      * ``overflow``  — fraction of (token, choice) assignments dropped at
        the capacity limit.  A collapsed router shows up HERE, not as a
        mysterious accuracy loss: dropped tokens pass through the residual.

    ``partition_experts`` adds the ``with_partitioning('expert', ...)``
    annotations the expert-parallel engine reads; leave False on meshes
    without an 'expert' axis (plain DP) — the annotation names a mesh axis,
    so it must only be present when that axis exists.
    """

    num_experts: int = 8
    hidden: int = 256
    capacity_factor: float = 1.25
    router_top_k: int = 1
    partition_experts: bool = False
    partition_model: bool = False   # ep×tp: Megatron-split each expert's FFN
                                    # over the 'model' axis on top of the
                                    # expert sharding (GShard's 2-D expert
                                    # layout); requires partition_experts
    group_size: int | None = None   # GShard G×S grouped routing: tokens
                                    # route in independent groups of S with
                                    # per-group capacity k·cf·S/E.  The
                                    # dispatch/combine einsums cost
                                    # O(S·T·d) instead of O(T²·d) (E·C ∝ S,
                                    # not T) — the lever that keeps the
                                    # dense-dispatch formulation linear in
                                    # tokens at transformer scale.  None or
                                    # non-dividing = one group (exact
                                    # original semantics).
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        if self.router_top_k not in (1, 2):
            raise ValueError(
                f"router_top_k must be 1 or 2, got {self.router_top_k}: "
                f"MoELayer is the capacity-limited Switch/GShard layer; "
                f"DroplessMoE routes any k without drops")
        tokens, d = x.shape
        e = self.num_experts
        gs = self.group_size
        if gs is not None and 0 < gs < tokens and tokens % gs == 0:
            g, s = tokens // gs, gs
        else:
            g, s = 1, tokens
        xg = x.reshape(g, s, d)
        # capacity scales with k (GShard): top-2 makes 2·s assignments per
        # group, so unscaled slots would drop ≥37% even under perfectly
        # uniform routing and the overflow metric would read ~0.4 forever
        capacity = max(1, int(self.router_top_k * self.capacity_factor
                              * s / e + 0.999999))

        # --- router (f32) ------------------------------------------------
        gate_w = self.param("gate", nn.initializers.lecun_normal(), (d, e),
                            jnp.float32)
        logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32), gate_w)
        probs = jax.nn.softmax(logits, axis=-1)
        top1 = jnp.argmax(probs, axis=-1)                       # [G, S]
        mask1 = jax.nn.one_hot(top1, e, dtype=jnp.float32)      # [G, S, E]

        # Switch aux loss: E · Σ_e (token fraction · mean router prob),
        # per group, averaged over groups (one group = original formula)
        aux = e * jnp.mean(jnp.sum(mask1.mean(axis=1) * probs.mean(axis=1),
                                   axis=-1))
        self.sow("intermediates", "aux_loss", aux)
        # router z-loss: keeps logits from drifting to magnitudes where
        # softmax saturates and routing gradients vanish
        self.sow("intermediates", "z_loss",
                 jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2))

        # --- capacity-limited dispatch/combine tensors -------------------
        if self.router_top_k == 1:
            gates = [probs]                  # top-1 gate = raw router prob
            masks = [mask1]
        else:
            # second choice: argmax with the first masked out; gates
            # renormalized over the chosen pair (GShard)
            probs2 = probs * (1.0 - mask1)
            mask2 = jax.nn.one_hot(jnp.argmax(probs2, axis=-1), e,
                                   dtype=jnp.float32)
            p1 = jnp.sum(probs * mask1, axis=-1, keepdims=True)
            p2 = jnp.sum(probs * mask2, axis=-1, keepdims=True)
            denom = jnp.maximum(p1 + p2, 1e-9)
            gates = [mask1 * (p1 / denom), mask2 * (p2 / denom)]
            masks = [mask1, mask2]

        dispatch = jnp.zeros((g, s, e, capacity), jnp.float32)
        combine = jnp.zeros((g, s, e, capacity), jnp.float32)
        offset = jnp.zeros((g, e), jnp.float32)  # slots claimed by earlier k
        assigned = kept = 0.0
        for mask, gate in zip(masks, gates):
            position = ((jnp.cumsum(mask, axis=1) - 1.0) * mask
                        + offset[:, None, :])
            keep = mask * (position < capacity)
            offset = offset + mask.sum(axis=1)
            pos_onehot = jax.nn.one_hot(position.astype(jnp.int32), capacity,
                                        dtype=jnp.float32)   # [G, S, E, C]
            dispatch = dispatch + keep[..., None] * pos_onehot
            combine = combine + keep[..., None] * pos_onehot * gate[..., None]
            assigned = assigned + mask.sum()
            kept = kept + keep.sum()

        self.sow("intermediates", "overflow",
                 1.0 - kept / jnp.maximum(assigned, 1.0))

        # --- expert FFN (stacked weights, expert axis sharded) -----------
        if self.partition_model and not self.partition_experts:
            raise ValueError(
                "partition_model on MoELayer means ep×tp (Megatron split "
                "inside each expert) and requires partition_experts=True")
        init1 = init2 = nn.initializers.lecun_normal()
        if self.partition_experts:
            # ep×tp: within each expert, w1 is column-parallel (hidden dim
            # sharded over 'model') and w2 row-parallel (contraction dim
            # sharded) — the [E/ep, C, hidden] activation stays model-sharded
            # between them and GSPMD emits one psum per expert FFN pair,
            # exactly the Megatron layout lifted over the stacked expert dim
            tp_axis = meshlib.MODEL_AXIS if self.partition_model else None
            init1 = nn.with_partitioning(
                nn.initializers.lecun_normal(),
                (meshlib.EXPERT_AXIS, None, tp_axis))
            init2 = nn.with_partitioning(
                nn.initializers.lecun_normal(),
                (meshlib.EXPERT_AXIS, tp_axis, None))
        w1 = self.param("w1", init1, (e, d, self.hidden), jnp.float32)
        w2 = self.param("w2", init2, (e, self.hidden, d), jnp.float32)

        expert_in = jnp.einsum("gsec,gsd->gecd", dispatch.astype(self.dtype),
                               xg.astype(self.dtype))
        h = jax.nn.relu(jnp.einsum("gecd,edh->gech", expert_in,
                                   w1.astype(self.dtype)))
        expert_out = jnp.einsum("gech,ehd->gecd", h, w2.astype(self.dtype))
        y = jnp.einsum("gsec,gecd->gsd", combine.astype(self.dtype),
                       expert_out)
        return y.reshape(tokens, d)


class DroplessMoE(nn.Module):
    """Dropless top-k routed experts with a shared expert, over tokens
    (leading axis of x).

    ``s = sigmoid(x W_g)`` in float32 over all ``num_experts``; the
    ``top_k`` experts of a token are the largest of ``s + b`` (``b`` the
    choice bias: it moves the choice and never the weight); their weights
    are ``s`` without ``b``, divided by their sum when ``norm_topk`` and
    times ``routed_scale``.  ``y = sum_i w_i E_i(x) + S(x)``, with
    ``expert_act`` the form of every ``E_i`` and of ``S``:

    * ``"swiglu"``: ``W_down(silu(W_gate x) * (W_up x))``, three matrices;
    * ``"relu2"``: ``W_down relu(W_up x)^2``, two matrices and no gate.

    ``E_i`` has width ``hidden``, ``S`` width ``shared_hidden`` (0 = none).
    With ``shared_experts = m > 1`` the shared part is the MEAN of ``m``
    shared experts of width ``shared_hidden / m`` each, ``(1/m) sum_j
    S_j(x)``, held as one product of width ``shared_hidden`` (the ``m``
    gate / up matrices side by side, the ``m`` down matrices stacked: the
    same sum) whose output is divided by ``m``.
    With ``latent`` the routed experts work in a latent of that width: the
    router and the shared expert read ``x``, the experts read ``u = x
    W_dn`` and write latents, and their weighted sum goes through ``W_up``
    back to the width of ``x``: ``y = (sum_i w_i E_i(u)) W_up + S(x)`` (no
    norm or bias on the two projections).  With ``expert_act="swiglu"``
    and ``latent=0`` the layer is what it was before it knew either.

    ``held = (first, count)`` names the experts whose weights live here
    (None = all).  The router keeps its full width; a (token, choice)
    pair whose expert is not held contributes nothing, so the layer
    returns its own experts' part of the result plus the shared expert —
    what one chip of an expert-parallel deployment computes before the
    exchange (tests/test_mla_moe.py and tests/test_hybrid_ssm.py add the
    shares up).

    Dispatch: the ``tokens * top_k`` pairs are sorted by expert (pairs
    that are not held, or whose token is not ``valid``, sort last and lie
    past the last group), the tokens gathered in that order, and the
    expert matrices applied as grouped products over the group sizes.  No
    capacity: every pair is computed.  Each token's expert choice is sown
    as ``expert_choice`` into ``intermediates`` (the serving engine counts
    routing load from it).

    ``token_block`` bounds the temporaries of a long block of tokens (a
    prefill bucket): the router scores all ``t`` tokens at once, and where
    ``t`` exceeds ``token_block`` everything after it (sort, gather,
    grouped products, shared expert) runs over ``token_block`` tokens at a
    time, so that the ``token_block * top_k`` gathered rows and their four
    companions are all that is live.  The result is the same sum.  With
    ``shared_experts`` 1 and ``token_block`` 0 the layer is what it was
    before it knew either."""

    num_experts: int = 8
    top_k: int = 2
    hidden: int = 256
    shared_hidden: int = 0
    routed_scale: float = 1.0
    norm_topk: bool = True
    held: tuple[int, int] | None = None
    expert_act: str = "swiglu"
    latent: int = 0
    shared_experts: int = 1
    token_block: int = 0
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, valid=None):
        t, d = x.shape
        e, k = self.num_experts, self.top_k
        first, n = self.held if self.held is not None else (0, e)
        if not (0 <= first and n >= 1 and first + n <= e and 1 <= k <= e):
            raise ValueError(
                f"held={self.held} / top_k={k} do not fit {e} experts")
        if self.expert_act not in ("swiglu", "relu2"):
            raise ValueError(f"expert_act {self.expert_act!r}: swiglu or "
                             f"relu2")
        init = nn.initializers.lecun_normal()

        # --- router (f32, true f32 products: a choice is a comparison) ---
        w_router = self.param("router", init, (d, e), self.param_dtype)
        bias = self.param("choice_bias", nn.initializers.zeros_init(), (e,),
                          jnp.float32)
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, choice = jax.lax.top_k(scores + bias, k)             # [T, k]
        weight = jnp.take_along_axis(scores, choice, axis=-1)
        if self.norm_topk:
            weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
        weight = weight * self.routed_scale
        self.sow("intermediates", "expert_choice", choice)

        local = choice - first
        here = (local >= 0) & (local < n)
        if valid is not None:
            here = here & valid[:, None]

        # modules and expert weights are made once, where first used, and
        # shared by every token block
        made = {}

        def once(name, make):
            if name not in made:
                made[name] = make()
            return made[name]

        def project(width, name):
            return once(name, lambda: nn.Dense(
                width, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, name=name))

        def tail(x, local, here, weight):
            """Everything after the router, over one block of tokens."""
            t = x.shape[0]
            # --- sort the pairs by held expert; the rest sort last -------
            local = jnp.where(here, local, n).reshape(-1)       # [T*k]
            order = jnp.argsort(local, stable=True)
            sizes = jnp.bincount(local, length=n + 1)[:n].astype(jnp.int32)

            # what the routed experts read: x, or its latent
            u = project(self.latent, "latent_down")(x) if self.latent \
                else x.astype(self.dtype)
            w = u.shape[-1]
            xs = u[order // k]                                  # [T*k, w]

            def grouped(rows, name, shape):
                weights = once(name, lambda: self.param(
                    name, init, (n,) + shape, self.param_dtype))
                return jax.lax.ragged_dot(rows, weights.astype(self.dtype),
                                          sizes)

            if self.expert_act == "swiglu":
                gate = grouped(xs, "w_gate", (w, self.hidden))
                up = grouped(xs, "w_up", (w, self.hidden))
                act = jax.nn.silu(gate) * up
            else:
                act = jnp.square(jax.nn.relu(
                    grouped(xs, "w_up", (w, self.hidden))))
            ys = grouped(act, "w_down", (self.hidden, w))
            # rows past the last group belong to no expert here: whatever
            # the grouped product left there is not a result
            ys = jnp.where((jnp.arange(t * k) < sizes.sum())[:, None], ys, 0)
            ys = ys[jnp.argsort(order)].reshape(t, k, w)        # unsort
            y = jnp.einsum("tkd,tk->td", ys.astype(jnp.float32),
                           jnp.where(here, weight, 0.0))
            y = y.astype(self.dtype)
            if self.latent:
                y = project(d, "latent_up")(y)
            if self.shared_hidden:
                kind = SwiGLU if self.expert_act == "swiglu" else ReLU2MLP
                shared = once("shared", lambda: kind(
                    self.shared_hidden, self.dtype, self.param_dtype,
                    name="shared"))(x)
                if self.shared_experts > 1:
                    shared = shared / self.shared_experts
                y = y + shared
            return y

        size = self.token_block
        if not size or t <= size:
            return tail(x, local, here, weight)
        return jnp.concatenate(
            [tail(*(a[lo:lo + size] for a in (x, local, here, weight)))
             for lo in range(0, t, size)], axis=0)


class ReLU2MLP(nn.Module):
    """``W_down relu(W_up x)^2``, no gate and no biases."""

    hidden: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        return dense(x.shape[-1], "down")(
            jnp.square(jax.nn.relu(dense(self.hidden, "up")(x))))


class SwiGLU(nn.Module):
    """``W_down(silu(W_gate x) * (W_up x))``, no biases."""

    hidden: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        h = jax.nn.silu(dense(self.hidden, "gate")(x)) \
            * dense(self.hidden, "up")(x)
        return dense(x.shape[-1], "down")(h)


_MOE_GROUP_TARGET = 1024  # ~GShard group size: big enough that per-group
                          # capacity statistics are stable, small enough
                          # that the T×(E·C) dispatch einsums stay linear
                          # in total tokens


_MOE_GROUP_FLOOR = 256    # below this, per-group capacity k·cf·S/E gets so
                          # small that ordinary routing imbalance inside a
                          # group drops tokens wholesale — better one big
                          # group (quadratic dispatch) than quality loss


def _moe_group_size(tokens: int, target: int = _MOE_GROUP_TARGET):
    """Largest power-of-two divisor of ``tokens`` in [floor, target]
    (static, trace-time).  None — one group, exact original semantics —
    when tokens already fit in ≤target, or when the only dividing
    power-of-two would make groups smaller than the floor (e.g. 2000
    tokens divide by 16 but not 512: tiny groups drop tokens under any
    routing imbalance, so the quadratic one-group dispatch is the better
    trade)."""
    if tokens <= target:
        return None
    s = target
    while s >= _MOE_GROUP_FLOOR and tokens % s:
        s //= 2
    return s if s >= _MOE_GROUP_FLOOR else None


def moe_ffn(x, *, hidden: int, moe_experts: int, moe_top_k: int,
            moe_capacity_factor: float, partition_experts: bool,
            partition_model: bool, dtype) -> jnp.ndarray:
    """Routed-FFN swap for a transformer block: (B, L, D) tokens →
    (B, L, D) through a MoELayer over the flattened B·L tokens, routed in
    GShard groups of ≤ _MOE_GROUP_TARGET tokens (see MoELayer.group_size —
    keeps the dispatch einsums linear in B·L at transformer scale).

    The single definition of the transformer-block MoE dispatch, shared
    by GPTBlock (models/gpt.py) and TransformerLayer (models/bert.py) so
    the two families cannot diverge.  Must be called inside the caller's
    ``@nn.compact`` ``__call__`` — the MoELayer submodule is created in
    the caller's flax scope (auto-named ``MoELayer_i`` there).
    ``partition_model`` only takes effect together with
    ``partition_experts`` (the GShard 2-D layout needs the expert axis
    first)."""
    b, l, d = x.shape
    y = MoELayer(num_experts=moe_experts, hidden=hidden,
                 capacity_factor=moe_capacity_factor,
                 router_top_k=moe_top_k,
                 partition_experts=partition_experts,
                 partition_model=partition_model and partition_experts,
                 group_size=_moe_group_size(b * l),
                 dtype=dtype)(x.reshape(b * l, d))
    return y.reshape(b, l, d)


class MoEClassifier(nn.Module):
    """embed → (residual MoE layer) × depth → head, over flattened inputs.

    Plays the reference model_fn role (reference initializer.py:12-21) for
    the expert-parallel mode: same (images → logits) contract as the MLP,
    with the hidden FFN replaced by routed experts.
    """

    num_classes: int = 10
    num_experts: int = 8
    embed_dim: int = 128
    expert_hidden: int = 256
    depth: int = 1
    capacity_factor: float = 1.25
    router_top_k: int = 1
    dropout_rate: float = 0.1
    partition_experts: bool = False
    partition_model: bool = False   # ep×tp (see MoELayer)
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype).reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(self.embed_dim, dtype=self.dtype)(x))
        for _ in range(self.depth):
            y = MoELayer(num_experts=self.num_experts,
                         hidden=self.expert_hidden,
                         capacity_factor=self.capacity_factor,
                         router_top_k=self.router_top_k,
                         partition_experts=self.partition_experts,
                         partition_model=self.partition_model,
                         dtype=self.dtype)(x)
            x = x + y  # residual: dropped (over-capacity) tokens pass through
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x.astype(jnp.float32)
