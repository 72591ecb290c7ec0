"""What one forward recurrence of a Kimi-Delta-Attention layer requires, worked
out from shapes (the yardstick's numbers, as roofline.py's are).

A token and head with D key and D value features and a state of D x D: the
state decays a channel (D^2 multiplications), is read for the key (2 D^2: a
multiply-add a state entry), takes the rank-one correction beta k (v - S^T k)^T
(2 D^2: the outer product and its addition) and is read out for the query (2
D^2), 7 D^2 in all. It prices the recurrence as written, so it is the same
whatever chunking, matmul form or kernel a program computes it with: the
pairwise decays, the triangular solve and the products inside a chunk that a
chunked form adds are not required work.

It crosses HBM with q, k, v and the output o once each in the compute dtype, the
log-decay a channel once in float32 (the program's own: its exponent's range is
what the chunked form's care is about) and beta a head once in float32. The
state never needs to leave the chip.
"""

from __future__ import annotations

from typing import Dict


def recurrence_cost(*, batch: int, seq: int, heads: int, head_dim: int, itemsize: int = 2) -> Dict[str, float]:
    """Operations and bytes of ONE forward recurrence over `batch` sequences of `seq`."""
    tokens = float(batch * seq)
    inner = heads * head_dim
    return {"ops": tokens * 7.0 * inner * head_dim,
            "bytes": tokens * (itemsize * 4 * inner + 4.0 * inner + 4.0 * heads)}
