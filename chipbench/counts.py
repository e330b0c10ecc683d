"""Operations and bytes the benchmarked work needs, from its shapes alone.

These are the yardstick's own counts: per-layer metrics divide them by
times read from the device trace.  Nothing here reads the program.
"""
from __future__ import annotations

import math

#: values per tile of the uplink kernel (``GROUP·R·LANES`` = 32·8·128)
TILE_VALS = 32768


def dense_param_count(cfg: dict) -> int:
    """Parameters of one copy of a dense decoder as the configuration
    file states it: embedding (and an untied head), per layer the q/k/v/o
    projections, a gated MLP and two norms, and the final norm."""
    d = cfg["hidden_size"]
    head_dim = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * head_dim
    kv = cfg["num_key_value_heads"] * head_dim
    n = cfg["vocab_size"] * d
    if not cfg["tie_word_embeddings"]:
        n += cfg["vocab_size"] * d
    layer = d * q + 2 * d * kv + q * d + 3 * d * cfg["intermediate_size"] + 2 * d
    return n + cfg["num_hidden_layers"] * layer + d


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward operations for one token, no recompute:
    ``6·N`` for the weights and ``12·L·S·D`` for the attention scores and
    their weighted sum, counted over the full ``S×S`` square (PaLM,
    arXiv 2204.02311, appendix B)."""
    n = dense_param_count(cfg)
    return 6.0 * n + 12.0 * cfg["num_hidden_layers"] * seq * cfg["hidden_size"]


def round_train_flops(cfg: dict, agents: int, batch: int, seq: int,
                      n_epochs: int) -> float:
    """Local-training operations of one federated round: every agent
    runs ``n_epochs`` forward and backward passes over its batch."""
    return train_flops_per_token(cfg, seq) * agents * batch * seq * n_epochs


def wire_bits(levels: int) -> int:
    """Bits of one level index of a uniform quantizer: ceil(log2(L+1))."""
    return max(1, math.ceil(math.log2(levels + 1)))


def quant_pipeline_bytes(leaf_sizes, itemsize: int, levels: int) -> int:
    """HBM bytes the fused quantize→EF→pack kernel moves over the given
    leaves: it reads the message and the EF cache and writes the packed
    words and the new cache, each over whole tiles.  Leaves under one
    tile do not go through the kernel."""
    total = 0
    bits = wire_bits(levels)
    for n in leaf_sizes:
        if n < TILE_VALS:
            continue
        vals = -(-n // TILE_VALS) * TILE_VALS
        total += 3 * vals * itemsize + vals * bits // 8
    return total


def dense_leaf_sizes(cfg: dict, agents: int) -> list:
    """Element counts of the agent-stacked parameter leaves the uplink
    sweeps: one leaf per kind of weight, stacked over the layers."""
    d = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    head_dim = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * head_dim
    sizes = [cfg["vocab_size"] * d, layers * d, layers * d, d]   # embed, norms
    sizes += [layers * d * d, layers * d * kv, layers * d * kv, layers * d * d]
    sizes += [layers * d * f] * 3
    if not cfg["tie_word_embeddings"]:
        sizes.append(d * cfg["vocab_size"])
    return [agents * n for n in sizes]
