"""``SlotContract``: what a served model family declares to
``serving/kv_cache.SlotKVCache``, the one object the table reads of it.
The contract, and how a family plugs in, is the ``serving/kv_cache.py``
module docstring; this module holds the declaration alone, so that a model
file imports it without the serving stack."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping

import numpy as np


def _no_attrs(*_) -> dict:
    return {}


@dataclasses.dataclass(frozen=True)
class SlotContract:
    """What a served model family declares to ``SlotKVCache``:
    ``slot_contract()`` on the module that ``slot_decode_clone`` returns
    gives one of these.

    ``takes_active``: the call takes the ``active`` (slots,) mask (a model
    with per-slot state keeps the state of a slot that is not active bit
    for bit; one with routed experts may keep such a slot's stale token
    from every expert; GPT-2 reads the prefix the active slots need).
    ``resumable_step``: the one-token step resumes from any position over
    per-head K/V rows, so chunk resume, the prefix pool, multi-step
    dispatch, speculative verify and KV handoff may be built from it.
    ``slot_state``: the per-slot state leaves of the ``cache`` collection
    by name, and whether the table's ``kv_dtype`` may narrow each.
    ``slot_rings``: the ring leaves by name, and their rows.
    ``expert_layers``, ``num_experts``, ``experts_per_token``,
    ``experts_held``: the layers with routed experts (0: none, and the
    step is ``kv_decode_step``; else ``kv_decode_step_routed``, which
    reads the choices the call sows into ``intermediates``), the router's
    width, the choices a token makes, and the ``(first, count)`` share of
    the experts held here (None: all of them).
    ``scan_layers``: the layers whose block prefill runs
    ``ops/selective_scan`` (``counters()``' ``ssm_scan_*``).
    ``step_param_dtype``: ``path -> dtype | None``, the dtype in which the
    call first uses the parameter at ``path`` (None: as held); None for a
    family that holds its parameters as it uses them.
    ``tensor_parallel``: the module splits its heads over the mesh's
    ``model`` axis, and the table its kv-head dim with them (otherwise
    both replicate over that axis).
    ``int8_rows``: the module stores its rows int8 when cloned with
    ``kv_quant=True`` (the table refuses int8 storage otherwise).
    ``round_attrs(lengths, advancing)`` and ``prefill_attrs(prompt_len)``:
    the span attributes the family reports for a decode round over the
    ``advancing`` slots at the host ``lengths`` before it, and for the
    prefill of a ``prompt_len``-token prompt."""

    takes_active: bool
    resumable_step: bool = False
    slot_state: Mapping[str, bool] = dataclasses.field(default_factory=dict)
    slot_rings: Mapping[str, int] = dataclasses.field(default_factory=dict)
    expert_layers: int = 0
    num_experts: int = 0
    experts_per_token: int = 0
    experts_held: tuple[int, int] | None = None
    scan_layers: int = 0
    step_param_dtype: Callable[[tuple[str, ...]], Any] | None = None
    tensor_parallel: bool = False
    int8_rows: bool = False
    round_attrs: Callable[[np.ndarray, np.ndarray], dict] = _no_attrs
    prefill_attrs: Callable[[int], dict] = _no_attrs
