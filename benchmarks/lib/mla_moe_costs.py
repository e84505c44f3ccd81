"""Operations and bytes the latent-attention, sparse-expert decoder needs,
from shapes alone (``cfg``: a ``config.json`` of the family).

Matmul FLOPs only (2 per multiply-add): norms, RoPE, the softmax, the
router's sigmoid and the embedding gather are left out.  A token passes the
ACTIVE parameters: the experts it chose, not all of them."""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    return dict(
        h=int(cfg["hidden_size"]), heads=int(cfg["num_attention_heads"]),
        dn=int(cfg["qk_nope_head_dim"]), dr=int(cfg["qk_rope_head_dim"]),
        dv=int(cfg["v_head_dim"]), r=int(cfg["kv_lora_rank"]),
        f=int(cfg["intermediate_size"]), m=int(cfg["moe_intermediate_size"]),
        e=int(cfg["n_routed_experts"]), k=int(cfg["num_experts_per_tok"]),
        shared=int(cfg["n_shared_experts"]), vocab=int(cfg["vocab_size"]),
        layers=int(cfg["num_hidden_layers"]),
        dense=int(cfg["first_k_dense_replace"]))


def attention_params(cfg: dict) -> int:
    """W_q, W_kva, W_kvb and W_o of one layer."""
    s = _sizes(cfg)
    return (s["h"] * s["heads"] * (s["dn"] + s["dr"])
            + s["h"] * (s["r"] + s["dr"])
            + s["r"] * s["heads"] * (s["dn"] + s["dv"])
            + s["heads"] * s["dv"] * s["h"])


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    s = _sizes(cfg)
    return 3 * s["h"] * s["m"]


def ffn_flops_per_token(cfg: dict) -> float:
    """All layers' feed-forward products for one token: the dense MLP in
    the leading layers; router, the chosen experts and the shared expert in
    the others."""
    s = _sizes(cfg)
    dense = 2.0 * 3 * s["h"] * s["f"]
    sparse = 2.0 * (s["h"] * s["e"] + s["k"] * expert_params(cfg)
                    + 3 * s["h"] * s["shared"] * s["m"])
    return s["dense"] * dense + (s["layers"] - s["dense"]) * sparse


def attention_flops(cfg: dict, form: str) -> tuple[float, float]:
    """All layers' attention for one token as ``(fixed, per key)`` FLOPs.
    ``expanded`` (prefill): the four projections, then scores over ``d_n +
    d_r`` and values over ``d_v`` a head a key.  ``absorbed`` (the decode
    step): W_kvb is applied to the query and to the output in place of the
    token's latent (``d_n r`` and ``r d_v`` a head, as many products as
    W_kvb has), scores run over ``r + d_r`` and values over ``r``."""
    s = _sizes(cfg)
    if form == "expanded":
        per_key = s["heads"] * (s["dn"] + s["dr"] + s["dv"])
    elif form == "absorbed":
        per_key = s["heads"] * (2 * s["r"] + s["dr"])
    else:
        raise ValueError(f"unknown form {form!r}")
    return (s["layers"] * 2.0 * attention_params(cfg),
            s["layers"] * 2.0 * per_key)


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs one served request needs: the prompt in the expanded
    form (the token at position p sees p + 1 keys), every generated token
    but the last in the absorbed form at its own position, the head once a
    generated token."""
    s = _sizes(cfg)
    fed = prompt_len + new_tokens - 1
    prompt_keys = prompt_len * (prompt_len + 1) / 2.0
    decode_keys = fed * (fed + 1) / 2.0 - prompt_keys
    (fixed_e, key_e), (fixed_a, key_a) = (attention_flops(cfg, form)
                                          for form in ("expanded", "absorbed"))
    return (fed * ffn_flops_per_token(cfg)
            + prompt_len * fixed_e + prompt_keys * key_e
            + (new_tokens - 1) * fixed_a + decode_keys * key_a
            + new_tokens * 2.0 * s["h"] * s["vocab"])


def decode_round_bytes(cfg: dict, experts_touched: float, live_tokens: float,
                       cache_bytes_per_token: float,
                       itemsize: int = 2) -> float:
    """Bytes one decode round has to read: every weight outside the routed
    experts once (attention, the dense MLP, routers, shared experts, the
    head), ``experts_touched`` experts in each expert layer, and the table
    rows of the ``live_tokens`` the round's streams have behind them."""
    s = _sizes(cfg)
    sparse_layers = s["layers"] - s["dense"]
    weights = (s["layers"] * attention_params(cfg)
               + s["dense"] * 3 * s["h"] * s["f"]
               + sparse_layers * (s["h"] * s["e"]
                                  + 3 * s["h"] * s["shared"] * s["m"])
               + s["h"] * s["vocab"])
    weights += sparse_layers * experts_touched * expert_params(cfg)
    return weights * itemsize + live_tokens * cache_bytes_per_token
