"""What one forward EVA attention requires, worked out from shapes (the
yardstick's numbers, as roofline.py's are).

A query of window W = i // w scores the keys of its own window up to itself
and one summary a chunk of every earlier window (ops/eva.py's docstring has
the layer): QK^T and PV are 2 D operations each a scored pair and head. The
pooling is two dot products of a key with a learned vector and two weighted
sums a key and head: 8 D. It prices the attention as defined, so it is the
same whatever computes it: flash kernels and a far kernel, plain products, or
one masked softmax; what a kernel visits and masks is not required work.

It crosses HBM with q, k, v and the output once each, and with the summaries
(one key and one value a chunk) once: written by the pooling, resident for
the head's queries after that.
"""

from __future__ import annotations

from typing import Dict


def visible_pairs(seq: int, window: int, chunk: int) -> Dict[str, int]:
    """(query, key) pairs of one head of one sequence: `local`, the causal
    pairs inside the seq / window windows; `far`, a window's queries against
    the window / chunk summaries of each window before it."""
    windows = seq // window
    return {"local": windows * window * (window + 1) // 2,
            "far": window * (window // chunk) * windows * (windows - 1) // 2}


def eva_fwd_cost(*, batch: int, seq: int, heads: int, head_dim: int, window: int, chunk: int,
                 itemsize: int = 2) -> Dict[str, float]:
    """Operations and bytes of ONE forward EVA attention over `batch`
    sequences of `seq`: the pooling, the local part and the far part."""
    pairs = visible_pairs(seq, window, chunk)
    rows = float(batch * heads)
    return {"ops": rows * (4.0 * head_dim * (pairs["local"] + pairs["far"]) + 8.0 * head_dim * seq),
            "bytes": rows * itemsize * head_dim * (4.0 * seq + 2.0 * (seq // chunk))}


def eva_far_fwd_cost(*, batch: int, seq: int, heads: int, head_dim: int, window: int, chunk: int,
                     itemsize: int = 2) -> Dict[str, float]:
    """The far part alone, as the kernel `eva_far_fwd` is given it: the far
    pairs' operations; q, the local part's output in and the merged output out,
    the summaries once, and the float32 lse in and out."""
    pairs = visible_pairs(seq, window, chunk)
    rows = float(batch * heads)
    return {"ops": rows * 4.0 * head_dim * pairs["far"],
            "bytes": rows * (itemsize * head_dim * (3.0 * seq + 2.0 * (seq // chunk)) + 2 * 4.0 * seq)}
