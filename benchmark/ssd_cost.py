"""What one forward selective scan of a state-space (Mamba-2) layer requires,
worked out from shapes (the yardstick's numbers, as roofline.py's are).

A token and head of P features with a state of P x N: the state decays (P N
multiplications), takes dt x (x) B (2 P N: the outer product and its
addition) and is read out against C (2 P N: a multiply-add a state entry), 5
P N in all, and D x is added (2 P). It prices the recurrence as written, so
it is the same whatever chunking, matmul form or kernel a program computes it
with: the intra-chunk products a chunked form adds are not required work.

It crosses HBM with x, B, C and the output y once each in the compute dtype
and the step dt once in float32. The state never needs to leave the chip.
"""

from __future__ import annotations

from typing import Dict


def scan_cost(*, batch: int, seq: int, heads: int, head_dim: int, state: int, groups: int,
              itemsize: int = 2) -> Dict[str, float]:
    """Operations and bytes of ONE forward scan over `batch` sequences of `seq`."""
    tokens = float(batch * seq)
    inner = heads * head_dim
    return {"ops": tokens * (5.0 * inner * state + 2.0 * inner),
            "bytes": tokens * (itemsize * (2 * inner + 2 * groups * state) + 4.0 * heads)}
