"""Operations and bytes the algorithm needs, from shapes alone.

``cfg`` is a configuration file's dict (GPT-2 ``config.json`` keys).
Matmul FLOPs only (2 per multiply-add); embedding gathers, layer norms and
the softmax are left out; recomputed operations never count."""

from __future__ import annotations


def _sizes(cfg: dict) -> tuple[int, int, int, int]:
    h = int(cfg["n_embd"])
    ffn = int(cfg.get("n_inner") or 4 * h)
    return h, int(cfg["n_layer"]), ffn, int(cfg["vocab_size"])


def param_count(cfg: dict) -> int:
    h, layers, ffn, vocab = _sizes(cfg)
    per_layer = 4 * h * h + 4 * h + 2 * h * ffn + ffn + h + 4 * h
    return vocab * h + int(cfg["n_positions"]) * h + layers * per_layer + 2 * h


def forward_flops_per_token(cfg: dict, keys: float, head: bool = True) -> float:
    """One token's forward pass attending to ``keys`` positions: QKV and
    output projections (8h^2), FFN (4 h ffn), scores and values (4 h keys)
    per layer, and the tied head (2 h V) where its logits are needed."""
    h, layers, ffn, vocab = _sizes(cfg)
    per_layer = 2.0 * h * (4 * h + 2 * ffn) + 4.0 * h * keys
    return layers * per_layer + (2.0 * h * vocab if head else 0.0)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of one trained token in a causal sequence of
    ``seq`` tokens (a token attends to seq/2 keys on average), times 3."""
    return 3.0 * forward_flops_per_token(cfg, seq / 2.0)


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs one served request needs: every prompt and generated
    token but the last passes through the layers at its own position; the
    head is needed once per generated token."""
    h, layers, ffn, vocab = _sizes(cfg)
    n = prompt_len + new_tokens - 1          # tokens fed through the layers
    per_tok = 2.0 * h * (4 * h + 2 * ffn)
    attn = 4.0 * h * (n * (n + 1) / 2.0)     # token at position p: p+1 keys
    return layers * (n * per_tok + attn) + new_tokens * 2.0 * h * vocab


def flash_fwd(batch: int, heads: int, seq: int, head_dim: int,
              itemsize: int = 2, causal: bool = True) -> dict:
    """Flash attention forward, one call: QK^T and PV (half under the
    causal mask); reads q, k, v and writes o once (lse is 1/head_dim of
    a tensor and is counted)."""
    share = 0.5 if causal else 1.0
    tensor = batch * heads * seq * head_dim * itemsize
    return {"flops": 4.0 * batch * heads * seq * seq * head_dim * share,
            "bytes": 4.0 * tensor + batch * heads * seq * 4}


def flash_bwd(batch: int, heads: int, seq: int, head_dim: int,
              itemsize: int = 2, causal: bool = True) -> dict:
    """Flash attention backward, one call of both kernels together: the
    five matmuls the algorithm needs (scores again, dP, dV, dK, dQ; the
    second kernel's own recomputation of scores and dP does not count);
    reads q, k, v, do and writes dq, dk, dv once, lse and delta counted."""
    share = 0.5 if causal else 1.0
    tensor = batch * heads * seq * head_dim * itemsize
    return {"flops": 10.0 * batch * heads * seq * seq * head_dim * share,
            "bytes": 7.0 * tensor + 2 * batch * heads * seq * 4}


def roofline_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which limit sets it."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
