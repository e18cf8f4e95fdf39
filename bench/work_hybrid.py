"""Work counts of the Zamba2 hybrid from shapes: parameters, and the HBM
bytes one decode step has to move. The Mamba-2 layers, the embedding, the
head and the logits are counted by ``bench/work.py``; this adds the shared
blocks, the sites and the KV caches. Both read the benchmark's own
configuration file (``bench/configs``), never the program's model code, so
a change to the program cannot change the yardstick.

Sizes used besides ``bench/work.py``'s (keys of a configuration file):
``attention_hidden_size``, ``n_heads_attn``, ``attn_head_dim``, ``ffn``,
``adapter_rank``, ``num_mem_blocks``, ``hybrid_layer_ids``.
"""
from __future__ import annotations

from typing import Dict

from bench import work
from bench.work import F32_BYTES


def block_params(c: Dict) -> int:
    """One shared block: its two RMSNorms, attention and gated MLP."""
    d, a, f = c["d_model"], c["attention_hidden_size"], c["ffn"]
    heads = c["n_heads_attn"] * c["attn_head_dim"]
    return a + 3 * a * heads + heads * d + d + 3 * d * f


def site_params(c: Dict) -> int:
    """One site: the LoRA on gate and up, and the linear."""
    d, r, f = c["d_model"], c["adapter_rank"], c["ffn"]
    return d * r + 2 * r * f + d * d


def param_count(c: Dict) -> int:
    return (work.param_count(c) + c["num_mem_blocks"] * block_params(c)
            + len(c["hybrid_layer_ids"]) * site_params(c))


def kv_bytes_per_position(c: Dict, dtype_bytes: int = F32_BYTES) -> int:
    """Keys and values of one position, over every site's cache."""
    return len(c["hybrid_layer_ids"]) * 2 * c["n_heads_attn"] * c["attn_head_dim"] * dtype_bytes


def decode_bytes(c: Dict, position: int, dtype_bytes: int = F32_BYTES) -> int:
    """HBM bytes one decode step at ``position`` (0-based) has to move:
    ``work.decode_bytes`` (the Mamba layers' weights and states, the tied
    table read whole by the head, the logits), every site's block and site
    weights once per application (each site reads its block's weights), and
    the KV caches' positions 0..position read and one position written."""
    sites = len(c["hybrid_layer_ids"]) * (block_params(c) + site_params(c)) * dtype_bytes
    kv = kv_bytes_per_position(c, dtype_bytes) * (position + 2)
    return work.decode_bytes(c, dtype_bytes) + sites + kv
