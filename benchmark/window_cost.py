"""What one windowed causal attention call requires, worked out from shapes
(the yardstick's numbers, as roofline.py's are).

Under a window W key j is live for query i iff i - W < j <= i, so query i
scores min(i + 1, W) keys: a sequence of S queries scores
sum_i min(i + 1, W) pairs, W (W + 1) / 2 + (S - W) W for W <= S. QK^T and PV
are 2 D operations each a scored pair and head. Every q, k, v and output row
and the float32 log-sum-exp row cross HBM once. Pairs a kernel visits and
masks are not required work and are not counted.
"""

from __future__ import annotations

from typing import Dict


def window_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a causal self-attention of `seq` positions must
    score under `window`."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def flash_win_fwd_cost(*, batch: int, seq: int, window: int, n_q_heads: int, n_kv_heads: int,
                       head_dim: int, itemsize: int = 2, lse_bytes: int = 4) -> Dict[str, float]:
    """The windowed training forward kernel: (B, H, S, D) self-attention that
    also writes the float32 log-sum-exp row the backward pass reads."""
    ops = 4.0 * batch * n_q_heads * head_dim * window_pairs(seq, window)
    nbytes = batch * itemsize * head_dim * seq * (2.0 * n_kv_heads + 2.0 * n_q_heads) \
        + batch * n_q_heads * seq * lse_bytes
    return {"ops": ops, "bytes": nbytes}
