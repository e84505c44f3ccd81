"""Operations and bytes the window-and-global-attention, sparse-expert
decoder needs, from shapes alone (``cfg``: a ``config.json`` of the family
as the benchmark cuts it; ``lib/window_moe_weights.sizes`` reads it).

Matmul FLOPs (2 per multiply-add): norms, the rotation, the softmax, the
gates, the router's sigmoid and the embedding gather are left out.  A token
passes the ACTIVE parameters, and of the experts it chose only those HELD
here: what the other chips of the deployment would compute is nobody's
work on this one.  Attention is counted at the keys a query SEES: ``min(t +
1, window)`` in a window layer."""

from __future__ import annotations

from benchmarks.lib import window_moe_weights as weights


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    s = weights.sizes(cfg)
    return 3 * s["h"] * s["m"]


def outside_experts(cfg: dict) -> int:
    """A layer's matrices outside its routed experts (the gain left out):
    attention, router, the shared experts."""
    s = weights.sizes(cfg)
    h = s["h"]
    return (2 * h * s["q"] + 2 * h * s["kv"] + h * s["router"]
            + 3 * h * s["shared"] * s["m"])


def token_flops(cfg: dict, held_per_layer: float) -> float:
    """All layers' products for one token, attention's keys apart, when it
    reaches ``held_per_layer`` experts held here in each layer."""
    layers = len(weights.sizes(cfg)["windowed"])
    return layers * 2.0 * (outside_experts(cfg)
                           + held_per_layer * expert_params(cfg))


def keys_seen(fed: int, window: int | None) -> float:
    """Keys the queries at positions ``0 .. fed - 1`` see, all together."""
    if window is None or fed <= window:
        return fed * (fed + 1) / 2.0
    return window * (window + 1) / 2.0 + (fed - window) * float(window)


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int,
                held_per_layer: float) -> float:
    """Forward FLOPs one served request needs: prompt and every generated
    token but the last pass the layers; a query scores and weighs the keys
    it sees in each layer; the head once a generated token."""
    s = weights.sizes(cfg)
    fed = prompt_len + new_tokens - 1
    keys = sum(keys_seen(fed, s["window"] if windowed else None)
               for windowed in s["windowed"])
    return (fed * token_flops(cfg, held_per_layer) + keys * 2.0 * 2 * s["q"]
            + new_tokens * 2.0 * s["h"] * s["vocab"])


def decode_round_bytes(cfg: dict, experts_touched: float, full_rows: float,
                       ring_rows: float, token_bytes: float,
                       ring_row_bytes: float, itemsize: int = 2) -> dict:
    """Bytes one decode round has to move, by part: every weight outside
    the routed experts once (attention, routers, shared experts, the tied
    embedding as the head), ``experts_touched`` held experts in each layer,
    ``full_rows`` positions of full-length rows (``token_bytes`` each, all
    full layers together) and ``ring_rows`` positions of ring rows
    (``ring_row_bytes`` each, all window layers together) behind the
    round's streams."""
    s = weights.sizes(cfg)
    layers = len(s["windowed"])
    return {"weights": (layers * outside_experts(cfg)
                        + s["h"] * s["vocab"]) * itemsize,
            "experts": layers * experts_touched * expert_params(cfg)
            * itemsize,
            "rows": full_rows * token_bytes,
            "rings": ring_rows * ring_row_bytes}
