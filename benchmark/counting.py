"""Client records -> end-to-end serving metrics, counted by event time.

Rule 2 of PERF.md: a token counts if it reached the client inside
[t0, t1), whichever request it belongs to; a first token or a finished
request likewise. Nothing is computed from completed requests' totals
over the window length, and a request still in flight at t1 is neither
attempted nor failed.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, List, Optional, Sequence


@dataclasses.dataclass
class RequestRecord:
    """What one client saw of one request, on the benchmark's own clock
    (time.perf_counter of the process that holds the chip)."""

    client: int
    ordinal: int
    turn: int
    prompt_tokens: int
    max_tokens: int
    t_due: float                      # when the client was free to send it
    t_submit: float                   # when the call into the router began
    token_times: List[float] = dataclasses.field(default_factory=list)
    t_done: Optional[float] = None    # the stream's final item arrived
    n_out: int = 0
    error: Optional[str] = None

    @property
    def t_first(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None


def _inside(t: Optional[float], t0: float, t1: float) -> bool:
    return t is not None and t0 <= t < t1


def tokens_in_window(records: Sequence[RequestRecord], t0: float, t1: float) -> int:
    return sum(1 for r in records for t in r.token_times if t0 <= t < t1)


def ttfts_in_window(records: Sequence[RequestRecord], t0: float, t1: float) -> List[float]:
    """Submit-to-first-token seconds of every first token that arrived
    inside the window (the request may have been sent before it)."""
    return [r.t_first - r.t_submit for r in records if _inside(r.t_first, t0, t1)]


def finished_in_window(records: Sequence[RequestRecord], t0: float, t1: float) -> List[RequestRecord]:
    return [r for r in records if _inside(r.t_done, t0, t1)]


def tpots_in_window(records: Sequence[RequestRecord], t0: float, t1: float) -> List[float]:
    """(t_last - t_first) / (n_out - 1) per request that finished inside
    the window. Tokens arrive in blocks, so per-token gaps are not used."""
    return [
        (r.token_times[-1] - r.token_times[0]) / (len(r.token_times) - 1)
        for r in finished_in_window(records, t0, t1)
        if r.error is None and len(r.token_times) > 1
    ]


def failures(records: Sequence[RequestRecord], t0: float, t1: float, vocab: int,
             answers: Dict[tuple, Sequence[int]]) -> List[str]:
    """Why each request that ended inside the window failed, if it did:
    an error, a wrong number of tokens, or a token outside the vocabulary."""
    out = []
    for r in finished_in_window(records, t0, t1):
        answer = answers.get((r.client, r.ordinal), ())
        if r.error is not None:
            out.append(f"client {r.client} request {r.ordinal}: {r.error}")
        elif len(answer) != r.max_tokens:
            out.append(f"client {r.client} request {r.ordinal}: "
                       f"{len(answer)} tokens, asked {r.max_tokens}")
        elif not all(isinstance(t, int) and 0 <= t < vocab for t in answer):
            out.append(f"client {r.client} request {r.ordinal}: token outside the vocabulary")
    return out


def mean(values: Sequence[float]) -> Optional[float]:
    return statistics.fmean(values) if values else None


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile, q in [0, 100]."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(0, min(len(ordered) - 1, int(round(q / 100.0 * (len(ordered) - 1)))))
    return ordered[rank]


def serving_end_to_end(records: Sequence[RequestRecord], t0: float, t1: float) -> Dict[str, Optional[float]]:
    ttft = mean(ttfts_in_window(records, t0, t1))
    tpot = mean(tpots_in_window(records, t0, t1))
    return {
        "serve_tokens_per_s": tokens_in_window(records, t0, t1) / (t1 - t0),
        "ttft_mean_ms": None if ttft is None else ttft * 1e3,
        "tpot_mean_ms": None if tpot is None else tpot * 1e3,
    }
