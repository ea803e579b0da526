"""Window arithmetic on the harness clock (``engine.now()``).

Every request's observations live in an :class:`Obs`: when it was due, when
the generator got round to submitting it, and when each output token reached
the host (read after every ``engine.step()``). The window is ``[w0, w1]``;
the reductions below are the end-to-end metrics' definitions."""

from __future__ import annotations

import dataclasses
from typing import Iterable, Optional


def percentile(vals: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (the program's ``metrics._p`` arithmetic):
    the sorted value at index ``int(q * n)``, clamped to the last."""
    s = sorted(vals)
    if not s:
        return None
    return s[min(len(s) - 1, int(q * len(s)))]


@dataclasses.dataclass
class Obs:
    key: str
    due: float
    submitted: float
    prompt_len: int
    history_len: int = 0
    max_new: int = 0
    admitted: Optional[float] = None  # engine's admit_time (same clock)
    tokens: list[float] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None

    @property
    def first_token(self) -> Optional[float]:
        return self.tokens[0] if self.tokens else None


def due_in(obs: Iterable[Obs], w0: float, w1: float) -> list[Obs]:
    return [o for o in obs if w0 <= o.due < w1]


def ttfts(obs: Iterable[Obs], w0: float, w1: float) -> list[float]:
    """TTFT of every request due in the window. One with no first token by
    ``w1`` enters at its elapsed time ``w1 - due`` (censored), so a stall at
    the end of the window raises the tail instead of leaving it."""
    out = []
    for o in due_in(obs, w0, w1):
        ft = o.first_token
        out.append((ft if ft is not None and ft <= w1 else w1) - o.due)
    return out


def inter_token_gaps(obs: Iterable[Obs], w0: float, w1: float) -> list[float]:
    """Every gap between consecutive output tokens of a request that ends
    inside the window, plus, for a request still decoding at ``w1``, the
    open gap since its last token (censored like TTFT)."""
    out = []
    for o in obs:
        ts = [t for t in o.tokens if t <= w1]
        for a, b in zip(ts, ts[1:]):
            if b >= w0:
                out.append(b - a)
        if ts and (o.finished is None or o.finished > w1) and len(ts) < o.max_new:
            if w1 > ts[-1]:
                out.append(w1 - ts[-1])
    return out


def tokens_in_window(obs: Iterable[Obs], w0: float, w1: float) -> int:
    """Prompt tokens of requests whose first token came in the window, plus
    every output token that reached the host in it."""
    n = 0
    for o in obs:
        ft = o.first_token
        if ft is not None and w0 <= ft <= w1:
            n += o.prompt_len
        n += sum(1 for t in o.tokens if w0 <= t <= w1)
    return n


def arrival_lags(obs: Iterable[Obs], w0: float, w1: float) -> list[float]:
    """How late the generator submitted each request due in the window."""
    return [o.submitted - o.due for o in due_in(obs, w0, w1)]


def queue_waits(obs: Iterable[Obs], w0: float, w1: float) -> list[float]:
    """Admission minus due time of each request due in the window; one not
    admitted by ``w1`` enters at ``w1 - due``."""
    out = []
    for o in due_in(obs, w0, w1):
        a = o.admitted
        out.append((a if a is not None and a <= w1 else w1) - o.due)
    return out
