"""What one forward gated short convolution requires, worked out from shapes
(the yardstick's numbers, as roofline.py's are).

A token and channel under K causal depthwise taps with no bias and no
activation, gated before and after: the gate B * X, K products with the taps,
K - 1 additions, the gate C * c: 2 K + 1 operations, 7 at K = 3. It prices the
op as written, so it is the same whatever fusion or kernel a program computes
it with.

It crosses HBM with B, C, X read and y written once each in the compute dtype
(16 KB a token at 2,048 channels in bfloat16); the taps are a few kilobytes and
the K - 1 rows before a tile need not leave the chip.
"""

from __future__ import annotations

from typing import Dict


def gated_conv_cost(*, batch: int, seq: int, channels: int, taps: int, itemsize: int = 2) -> Dict[str, float]:
    """Operations and bytes of ONE forward gated convolution over `batch` sequences of `seq`."""
    tokens = float(batch * seq)
    return {"ops": tokens * (2.0 * taps + 1.0) * channels,
            "bytes": tokens * itemsize * 4.0 * channels}
