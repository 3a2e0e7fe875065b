"""Operations and bytes that the benchmark's roofline and utilization
metrics divide by.  They are computed here from the configuration's
published widths and the shapes the wrapper read, never taken from the
program, so a later change to the program cannot change them."""
from __future__ import annotations


def dense_param_count(m: dict) -> int:
    """Parameters of one dense decoder as the configuration states it:
    embedding (tied to the output head when ``tie_embeddings``), per layer
    q/k/v/o projections, a gated MLP and two norms, and the final norm."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * hd * (2 * m["num_heads"] + 2 * m["num_kv_heads"])
    mlp = 3 * d * m["d_ff"]
    n = m["vocab_size"] * d * (1 if m["tie_embeddings"] else 2)
    return n + m["num_layers"] * (attn + mlp + 2 * d) + d


def decode_flops(m: dict, tokens: int) -> float:
    """Model FLOPs of ``tokens`` generated tokens: 2 per parameter per
    token (the tied head counted once, the embedding gather not at all)."""
    return 2.0 * dense_param_count(m) * tokens


def topk_least_time(n_valid: float, dim: int, n_queries: int,
                    itemsize: int, peak: dict):
    """Least time one exact inner-product top-k over ``n_valid`` stored rows
    can take: (seconds, "memory" | "compute").  The search reads every valid
    row once and each query once (f32) and does 2 FLOPs per row element per
    query."""
    flops = 2.0 * n_valid * dim * n_queries
    nbytes = n_valid * dim * itemsize + n_queries * dim * 4
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_m, "memory") if t_m >= t_c else (t_c, "compute")
