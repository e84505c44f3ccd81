"""Operations and bytes the sparse-attention / softmax-expert decoder
needs, from shapes alone (``cfg``: a ``config.json`` of the family;
``lib/keye_weights.sizes`` reads it).

Matmul FLOPs (2 per multiply-add): norms, rope, the softmax, the
selection's counting and the embedding gather are left out.  A token at
position ``p`` scores ``p + 1`` keys with the indexer (``2 Hi Di`` FLOPs
each) and attends ``min(p + 1, top_k)`` of them (``4 Hq d`` each: scores and
values).  The kernels' counts are what the work needs: each operand read
once and each result written once, so a share of the roofline built on
them cannot pass 100%."""

from __future__ import annotations

from benchmarks.lib import keye_weights as weights


def top_k(cfg: dict) -> int:
    return int(cfg["sa_config"]["topk"])


def layer_matrices(cfg: dict) -> dict:
    """A layer's matrices by part (gains and the indexer's LayerNorm left
    out); ``experts`` is all of them, ``expert`` one."""
    s = weights.sizes(cfg)
    h, hi, di = s["h"], s["index_heads"], s["index_dim"]
    expert = 3 * h * s["ffn"]
    return {"attention": 2 * h * s["q"] + 2 * h * s["kv"],
            "indexer": h * (hi * di + di + hi),
            "router": h * s["experts"],
            "expert": expert, "experts": s["experts"] * expert}


def token_flops(cfg: dict) -> float:
    """All layers' products for one token, keys apart: twice the matrix
    parameters it passes (its ``num_experts_per_tok`` experts)."""
    s, m = weights.sizes(cfg), layer_matrices(cfg)
    per_layer = m["attention"] + m["indexer"] + m["router"] \
        + s["per_token"] * m["expert"]
    return 2.0 * s["layers"] * per_layer


def _pairs(n: int, k: int) -> tuple[float, float]:
    """Over the positions ``0 .. n - 1``: the keys scored (``p + 1`` each)
    and the keys attended (at most ``k`` each)."""
    near = min(n, k)
    return n * (n + 1) / 2.0, near * (near + 1) / 2.0 + (n - near) * float(k)


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs one served request needs: prompt and every generated
    token but the last pass the layers, score their keys and attend their
    selection; the head once a generated token."""
    s = weights.sizes(cfg)
    fed = prompt_len + new_tokens - 1
    scored, attended = _pairs(fed, top_k(cfg))
    per_layer = scored * 2.0 * s["index_heads"] * s["index_dim"] \
        + attended * 4.0 * s["q"]
    return (fed * token_flops(cfg) + s["layers"] * per_layer
            + new_tokens * 2.0 * s["h"] * s["vocab"])


def prefill_call(cfg: dict, length: int) -> dict:
    """One call of ``dsa_prefill`` over ``length`` positions: the indexer's
    and the attention's FLOPs of the causal pairs and the selection, and
    the bytes of its operands (q, k, v, qI, kI, w) read once and its output
    written once, bfloat16 but ``w`` (float32)."""
    s = weights.sizes(cfg)
    scored, attended = _pairs(int(length), top_k(cfg))
    hi, di = s["index_heads"], s["index_dim"]
    row = 2 * (2 * s["q"] + 2 * s["kv"] + hi * di + di) + 4 * hi
    return {"flops": scored * 2.0 * hi * di + attended * 4.0 * s["q"],
            "bytes": float(length) * row}


def step_kernels(cfg: dict, index_rows: float, attended_rows: float,
                 slots: int) -> dict:
    """The step's two kernels over one round, all layers (``index_rows``
    and ``attended_rows`` summed over the slots and the layers, as the
    ``decode_step`` span carries them): the index rows scored and the
    selected K/V rows attended, each read once; the queries, the weights of
    the indexer heads and the outputs of ``slots`` slots a layer."""
    s = weights.sizes(cfg)
    hi, di = s["index_heads"], s["index_dim"]
    per_slot = 2 * (hi * di + 2 * s["q"]) + 4 * hi
    return {"flops": index_rows * 2.0 * hi * di
            + attended_rows * 4.0 * s["q"],
            "bytes": index_rows * 2.0 * di + attended_rows * 2.0 * 2 * s["kv"]
            + s["layers"] * slots * per_slot}


def decode_round_bytes(cfg: dict, experts_touched: float, index_rows: float,
                       attended_rows: float, itemsize: int = 2) -> dict:
    """Bytes one decode round has to move, by part: every matrix outside
    the experts and the head once, the experts the round touched
    (``experts_touched`` a layer, the span's), the index rows its slots
    scored and the K/V rows they attended (the span's, over the layers)."""
    s, m = weights.sizes(cfg), layer_matrices(cfg)
    dense = s["layers"] * (m["attention"] + m["indexer"] + m["router"]) \
        + s["h"] * s["vocab"]
    return {"weights": dense * float(itemsize),
            "experts": s["layers"] * experts_touched * m["expert"]
            * float(itemsize),
            "index_rows": index_rows * s["index_dim"] * float(itemsize),
            "kv_rows": attended_rows * 2.0 * s["kv"] * float(itemsize)}
