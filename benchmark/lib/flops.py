"""Operations and bytes the algorithm needs, from shapes alone. Recomputed
work (remat, a flash kernel's second pass over the scores) never counts.

A GPT-2 block at width d with a 4d MLP holds 12 d^2 weights in its four
matrices; embeddings are looked up, not multiplied, and the head reuses the
token embedding (d x vocab) as a matrix.
"""

from __future__ import annotations


def gpt_matmul_params(num_layers: int, embed_dim: int, vocab_size: int,
                      mlp_ratio: int = 4) -> int:
    """Weights that take part in a matrix multiplication per token."""
    per_block = (4 + 2 * mlp_ratio) * embed_dim * embed_dim
    return num_layers * per_block + embed_dim * vocab_size


def gpt_train_flops_per_token(num_layers: int, embed_dim: int,
                              vocab_size: int, seq_len: int,
                              mlp_ratio: int = 4) -> float:
    """Forward plus backward: 6 per matmul weight, plus causal attention's
    QK^T and PV (2 * 2 * seq * d per layer forward over the full square,
    halved for the causal mask, tripled for forward + backward)."""
    dense = 6.0 * gpt_matmul_params(num_layers, embed_dim, vocab_size, mlp_ratio)
    attention = 3.0 * num_layers * (4.0 * seq_len * embed_dim) / 2.0
    return dense + attention


def paged_decode_attention_cost(context_len: int, num_heads: int,
                                head_dim: int, kv_bytes: int = 2) -> dict:
    """One decode token's attention over `context_len` cached positions in
    one layer: QK^T and PV are 2 * 2 * context * heads * head_dim operations,
    and every cached K and V byte is read once. For `<kernel>_roofline` once
    the program's spans tie a kernel call to its context lengths (PERF.md,
    Open questions)."""
    width = num_heads * head_dim
    return {
        "flops": 4.0 * context_len * width,
        "bytes": 2.0 * context_len * width * kv_bytes,
    }


def causal_attention_cost(seq_len: int, num_heads: int, head_dim: int,
                          act_bytes: int = 2) -> dict:
    """Causal self-attention over one sequence in one layer, forward only:
    half the square of QK^T and PV; q, k, v read and the output written
    once."""
    width = num_heads * head_dim
    return {
        "flops": 4.0 * seq_len * seq_len * width / 2.0,
        "bytes": 4.0 * seq_len * width * act_bytes,
    }


def roofline_share(flops: float, bytes_moved: float, seconds: float,
                   peaks: dict) -> dict:
    """The least time the chip could take over the time it took, and which
    peak bounds it."""
    compute_s = flops / peaks["bf16_flops_per_s"]
    memory_s = bytes_moved / peaks["hbm_bytes_per_s"]
    return {
        "share": max(compute_s, memory_s) / seconds,
        "bound": "compute" if compute_s >= memory_s else "memory",
    }
