"""Operations and bytes the selective-scan / multi-query-attention hybrid
decoder needs, from shapes alone (``cfg``: a ``config.json`` of the family;
``lib/jamba_weights.sizes`` reads it).

Matmul FLOPs (2 per multiply-add): norms, the convolution's taps, the
softmax, the gates and the embedding gather are left out, and so is the
selective scan's elementwise work (it never reaches the MXU and is not
counted against the bfloat16 peak: ``selective_scan_call`` has its bytes)."""

from __future__ import annotations

from benchmarks.lib import jamba_weights as weights


def matrix_params(cfg: dict, kind: str) -> int:
    """A layer's matrices (gains, biases and the per-channel vectors left
    out): the mixer's and the SwiGLU's."""
    s = weights.sizes(cfg)
    h, di, n, r = s["h"], s["di"], s["state"], s["rank"]
    ffn = 3 * h * s["ffn"]
    if kind == "M":
        return h * 2 * di + di * (r + 2 * n) + r * di + di * h + ffn
    return 2 * h * s["q"] + 2 * h * s["kv"] + ffn


def token_flops(cfg: dict) -> float:
    """All layers' products for one token, attention's keys apart: twice
    the parameters outside the embedding."""
    return 2.0 * sum(matrix_params(cfg, kind)
                     for kind in weights.sizes(cfg)["pattern"])


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs one served request needs: prompt and every generated
    token but the last pass the layers; a token at position p scores and
    weighs p + 1 keys in each attention layer; the head once a generated
    token."""
    s = weights.sizes(cfg)
    fed = prompt_len + new_tokens - 1
    keys = fed * (fed + 1) / 2.0
    per_key = 2.0 * 2 * s["q"] * s["pattern"].count("A")
    return (fed * token_flops(cfg) + keys * per_key
            + new_tokens * 2.0 * s["h"] * s["vocab"])


def selective_scan_call(batch: int, length: int, channels: int,
                        state: int) -> dict:
    """One call of the selective-scan kernel as the program makes it: no
    matrix product; it reads ``u`` and ``dt`` and writes ``y`` (float32,
    ``length x channels`` each), reads ``B`` and ``C`` (``length x
    state``), ``A`` and ``D``, and reads and writes a state.  What the
    vector unit does (an exponential and six multiply-adds a state element
    a position) is in no roofline."""
    seq, st = batch * length * channels, batch * channels * state
    return {"flops": 0.0,
            "bytes": 4.0 * (3 * seq + 2 * batch * length * state
                            + channels * state + channels + 2 * st)}


def decode_round_bytes(cfg: dict, active: float, context: float,
                       state_bytes_per_slot: float,
                       cache_bytes_per_token: float,
                       itemsize: int = 2) -> dict:
    """Bytes one decode round has to move, by part: every matrix once and
    the tied embedding once as the head (``itemsize`` each) with the
    float32 ``A_log``, ``D`` and ``b_dt``, the state of the ``active`` slots
    read and written, and the attention rows the round's streams have
    behind them (``context`` tokens each)."""
    s = weights.sizes(cfg)
    kinds = s["pattern"]
    matrices = sum(matrix_params(cfg, kind) for kind in kinds) \
        + s["h"] * s["vocab"]
    vectors = kinds.count("M") * s["di"] * (s["state"] + 2)
    return {"weights": matrices * itemsize + vectors * 4.0,
            "state": 2.0 * active * state_bytes_per_slot,
            "rows": active * context * cache_bytes_per_token}
