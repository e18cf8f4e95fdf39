"""Work counts from shapes: parameters, model FLOPs of a train step, and the
HBM bytes a decode step has to move. They read the benchmark's own
configuration files (``bench/configs``), never the program's model code, so
a change to the program cannot change the yardstick.

Sizes used (keys of a configuration file): ``d_model``, ``n_layer``,
``vocab_padded``, ``d_state``, ``d_conv``, ``expand``, ``headdim``,
``ngroups``, ``chunk_size``, ``tie_embeddings``.
"""
from __future__ import annotations

from typing import Dict

F32_BYTES = 4


def _ssm(c: Dict) -> Dict[str, int]:
    d = c["d_model"]
    di = c["expand"] * d
    nh = di // c["headdim"]
    gn = c["ngroups"] * c["d_state"]
    conv_ch = di + 2 * gn
    matmul = d * (2 * di + 2 * gn + nh) + di * d  # in-projections, out_proj
    conv = c["d_conv"] * conv_ch
    other = conv_ch + 3 * nh + di + d  # conv bias, A/D/dt bias, gate norm, ln1
    return {"di": di, "nh": nh, "gn": gn, "conv_ch": conv_ch,
            "matmul": matmul, "conv": conv, "total": matmul + conv + other}


def param_count(c: Dict) -> int:
    d, v = c["d_model"], c["vocab_padded"]
    total = v * d * (1 if c["tie_embeddings"] else 2) + d  # embed, head, ln_f
    return total + c["n_layer"] * _ssm(c)["total"]


def _ssd_flops_per_token(c: Dict) -> int:
    """Forward FLOPs per token of one chunked SSD layer (arXiv:2405.21060
    §6): the intra-chunk C·Bᵀ and its product with x over a whole chunk, the
    chunk state B·x, and the state read-out C·h."""
    s = _ssm(c)
    L, N, P = c["chunk_size"], c["d_state"], c["headdim"]
    return 2 * L * (c["ngroups"] * N + s["nh"] * P) + 4 * s["nh"] * P * N


def train_flops_per_token(c: Dict) -> int:
    """Model FLOPs of forward and backward for one token: 6 per weight of
    every matmul (the embedding gather is none), the depthwise conv counted
    like a matmul of its weights, and 3 times the SSD forward terms.
    Recomputation under remat is not counted."""
    d, v = c["d_model"], c["vocab_padded"]
    s = _ssm(c)
    flops = 6 * v * d  # LM head
    return flops + c["n_layer"] * (6 * (s["matmul"] + s["conv"]) + 3 * _ssd_flops_per_token(c))


def train_flops_per_step(c: Dict, batch: int, seq_len: int) -> int:
    return batch * seq_len * train_flops_per_token(c)


def decode_bytes(c: Dict, dtype_bytes: int = F32_BYTES) -> int:
    """HBM bytes one decode step has to move: every weight it multiplies by,
    one embedding row (in the head's table where the head is tied), each SSM
    layer's conv window and state read and written, and the logits
    written. No term grows with the cache position."""
    d, v = c["d_model"], c["vocab_padded"]
    s = _ssm(c)
    weights = v * d + d  # LM head (read whole), ln_f
    if not c["tie_embeddings"]:
        weights += d  # one embedding row
    weights += c["n_layer"] * s["total"]
    cache = c["n_layer"] * 2 * ((c["d_conv"] - 1) * s["conv_ch"] + s["nh"] * c["headdim"] * c["d_state"])
    return (weights + cache + v) * dtype_bytes
