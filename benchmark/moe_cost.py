"""What one grouped matmul of a dropless MoE layer requires, worked out
from shapes (the yardstick's numbers, as roofline.py's are).

One projection of the expert layer maps `rows` routed (token, choice) rows
of width `k` to width `n` through `groups` expert matrices (k, n). Its
three products each need 2 * rows * k * n operations:

  fwd    out[r]  = lhs[r] @ rhs[group(r)]
  dlhs   dlhs[r] = dout[r] @ rhs[group(r)]^T
  drhs   drhs[g] = sum over the rows r of g of lhs[r]^T dout[r]

and cross HBM with every row read or written once and every expert's
matrix once. Rows a kernel adds to pad a group to its tile are not
required work and are not counted.
"""

from __future__ import annotations

from typing import Dict



def gmm_cost(*, rows: int, k: int, n: int, groups: int, itemsize: int = 2) -> Dict[str, float]:
    """Operations and bytes of one product (the same for all three: each
    reads or writes the (rows, k) side, the (rows, n) side and the
    (groups, k, n) matrices once; swapping k and n, as a SwiGLU layer's
    down projection does against its gate and up, changes neither)."""
    return {"ops": 2.0 * rows * k * n,
            "bytes": float(itemsize) * (rows * k + rows * n + groups * k * n)}
