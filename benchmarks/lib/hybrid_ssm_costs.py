"""Operations and bytes the hybrid state-space / attention / latent-expert
decoder needs, from shapes alone (``cfg``: a ``config.json`` of the family
as the benchmark cuts it; ``lib/hybrid_ssm_weights.sizes`` reads it).

Matmul FLOPs (2 per multiply-add) and the recurrence's own multiply-adds:
norms, the convolution's four taps, the softmax, the gates, the router's
sigmoid and the embedding gather are left out.  A token passes the ACTIVE
parameters, and of the experts it chose only those HELD here: what the
other chips of the deployment would compute is nobody's work on this
one."""

from __future__ import annotations

from benchmarks.lib import hybrid_ssm_weights as weights


def expert_params(cfg: dict) -> int:
    """One routed expert's two matrices."""
    s = weights.sizes(cfg)
    return 2 * s["latent"] * s["m"]


def outside_experts(cfg: dict, kind: str) -> int:
    """A layer's matrices outside its routed experts (gains and the small
    per-head vectors left out)."""
    s = weights.sizes(cfg)
    h = s["h"]
    if kind == "M":
        return h * (s["di"] + s["width"] + s["ssm_heads"]) + s["di"] * h
    if kind == "*":
        return 2 * h * s["q"] + 2 * h * s["kv"]
    return (h * s["router"] + 2 * h * s["latent"] + 2 * h * s["shared"])


def recurrence_flops(cfg: dict) -> float:
    """One state-space layer's recurrence for one token: ``dt x B^T`` into
    the state and ``S C`` out of it, a multiply-add an element each.  (The
    chunked form the prefill runs spends more; what an algorithm recomputes
    is not counted.)"""
    s = weights.sizes(cfg)
    return 2.0 * 2 * s["ssm_heads"] * s["ssm_head_dim"] * s["state"]


def token_flops(cfg: dict, held_per_layer: float) -> float:
    """All layers' products for one token, attention's keys apart, when it
    reaches ``held_per_layer`` experts held here in each expert layer."""
    s = weights.sizes(cfg)
    total = 0.0
    for kind in s["pattern"]:
        total += 2.0 * outside_experts(cfg, kind)
        if kind == "M":
            total += recurrence_flops(cfg)
        elif kind == "E":
            total += 2.0 * held_per_layer * expert_params(cfg)
    return total


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int,
                held_per_layer: float) -> float:
    """Forward FLOPs one served request needs: prompt and every generated
    token but the last pass the layers; a token at position p scores and
    weighs p + 1 keys in each attention layer; the head once a generated
    token."""
    s = weights.sizes(cfg)
    fed = prompt_len + new_tokens - 1
    keys = fed * (fed + 1) / 2.0
    per_key = 2.0 * 2 * s["q"] * s["pattern"].count("*")
    return (fed * token_flops(cfg, held_per_layer) + keys * per_key
            + new_tokens * 2.0 * s["h"] * s["vocab"])


def decode_round_bytes(cfg: dict, active: float, experts_touched: float,
                       context: float, state_bytes_per_slot: float,
                       cache_bytes_per_token: float,
                       itemsize: int = 2) -> dict:
    """Bytes one decode round has to move, by part: every weight outside
    the routed experts once (mixers, routers, latent projections, shared
    experts, the head), ``experts_touched`` held experts in each expert
    layer, the state of the ``active`` slots read and written, and the
    attention rows the round's streams have behind them (``context``
    tokens each)."""
    s = weights.sizes(cfg)
    fixed = sum(outside_experts(cfg, kind) for kind in s["pattern"]) \
        + s["h"] * s["vocab"]
    return {"weights": fixed * itemsize,
            "experts": (s["pattern"].count("E") * experts_touched
                        * expert_params(cfg) * itemsize),
            "state": 2.0 * active * state_bytes_per_slot,
            "rows": active * context * cache_bytes_per_token}
