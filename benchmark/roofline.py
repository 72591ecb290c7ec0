"""Peaks of the devices the benchmark knows, and what each kernel and each
training token require in operations and bytes, worked out from shapes.

These are the yardstick's numbers: a roofline share is required work over
(time x peak), so it can only pass 100% if a function here counts too
much or a time leaves out part of the work.
"""

from __future__ import annotations

from typing import Dict

# keyed by jax's device_kind. Source: Google Cloud documentation, "TPU v5e"
# (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add the device with its source, never a default"
        ) from None


def causal_pairs(q_len: int, kv_len: int) -> int:
    """(query, key) pairs a causal attention must score when the q_len
    queries are the last q_len positions of a kv_len-long context."""
    return q_len * (kv_len - q_len) + q_len * (q_len + 1) // 2


def attention_cost(*, q_len: int, kv_len: int, n_q_heads: int, n_kv_heads: int,
                   head_dim: int, itemsize: int, batch: int = 1,
                   lse_bytes: int = 0) -> Dict[str, float]:
    """Required work of one causal attention call: QK^T and PV are 2*D
    operations each per scored pair and head; every K/V row, every Q row
    and every output row crosses HBM once."""
    ops = 4.0 * batch * n_q_heads * head_dim * causal_pairs(q_len, kv_len)
    nbytes = batch * itemsize * head_dim * (
        2.0 * n_kv_heads * kv_len + 2.0 * n_q_heads * q_len
    ) + batch * n_q_heads * q_len * lse_bytes
    return {"ops": ops, "bytes": nbytes}


def flash_fwd_cost(*, batch: int, seq: int, n_q_heads: int, n_kv_heads: int,
                   head_dim: int, itemsize: int = 2) -> Dict[str, float]:
    """The training forward kernel: (B, H, S, D) causal self-attention that
    also writes the float32 log-sum-exp row the backward pass reads."""
    return attention_cost(q_len=seq, kv_len=seq, n_q_heads=n_q_heads,
                          n_kv_heads=n_kv_heads, head_dim=head_dim,
                          itemsize=itemsize, batch=batch, lse_bytes=4)


def ragged_attention_cost(*, q_len: int, kv_len: int, n_q_heads: int,
                          n_kv_heads: int, head_dim: int,
                          itemsize: int = 2) -> Dict[str, float]:
    """One sequence of a ragged_paged_attention call: a prefill chunk
    (q_len = chunk tokens, kv_len = offset + chunk tokens) or a decode
    lane (q_len = 1, kv_len = position + 1)."""
    return attention_cost(q_len=q_len, kv_len=kv_len, n_q_heads=n_q_heads,
                          n_kv_heads=n_kv_heads, head_dim=head_dim,
                          itemsize=itemsize)


def roofline_seconds(cost: Dict[str, float], device_kind: str) -> Dict[str, object]:
    """The least time the chip could take for `cost`, and which of the two
    peaks bounds it."""
    peak = peaks(device_kind)
    compute = cost["ops"] / peak["flops_per_s"]
    memory = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}


def train_flops_per_token(*, n_layers: int, d_model: int, n_q_heads: int,
                          n_kv_heads: int, head_dim: int, d_ff: int,
                          vocab: int, seq: int, gated_mlp: bool) -> float:
    """Operations the forward and backward passes REQUIRE per trained
    token (recomputation does not count): 2 per weight of every matmul
    and 4*D per causally visible key and head in the forward pass, times
    three for forward plus backward. The embedding lookup is no matmul."""
    attn_proj = d_model * head_dim * (2 * n_q_heads + 2 * n_kv_heads)
    mlp = d_model * d_ff * (3 if gated_mlp else 2)
    matmul_weights = n_layers * (attn_proj + mlp) + d_model * vocab
    attention = n_layers * 4.0 * n_q_heads * head_dim * (seq + 1) / 2.0
    return 3.0 * (2.0 * matmul_weights + attention)
