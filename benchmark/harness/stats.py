"""Reduction of the load generator's records to numbers. No JAX.

A record is one request as the client saw it, times in seconds relative
to the opening of the measured window:
  {"phase", "due_s", "sent_s", "token_s": [...], "asked", "ok", "error"}
Window accounting (benchmark/README.md): a request counts for time to
first token when it was DUE inside the window; a gap counts when its
LATER token arrived inside the window; a token counts for throughput
when it arrived inside the window.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """q in [0, 100], linear interpolation between order statistics
    (numpy's default 'linear' method); None for no samples."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(t: float, seconds: float) -> bool:
    return 0.0 <= t < seconds


def due_in_window(records: Iterable[Dict], seconds: float) -> List[Dict]:
    return [r for r in records if in_window(r["due_s"], seconds)]


def ttft_ms(records: Iterable[Dict], seconds: float) -> List[float]:
    """Due time -> first token, for requests due inside the window. A
    request that produced no token has no sample here; it is counted
    among `failed` and misses every service level."""
    return [(r["token_s"][0] - r["due_s"]) * 1e3
            for r in due_in_window(records, seconds) if r["token_s"]]


def pooled_gaps_ms(records: Iterable[Dict], seconds: float) -> List[float]:
    """Every gap between consecutive tokens of every stream whose later
    token arrived inside the window, pooled."""
    out: List[float] = []
    for r in records:
        ts = r["token_s"]
        out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:])
                   if in_window(b, seconds))
    return out


def tokens_in_window(records: Iterable[Dict], seconds: float) -> int:
    return sum(1 for r in records for t in r["token_s"]
               if in_window(t, seconds))


def lag_ms(records: Iterable[Dict], seconds: float) -> List[float]:
    """How late the generator sent each open-loop request."""
    return [(r["sent_s"] - r["due_s"]) * 1e3
            for r in due_in_window(records, seconds)
            if r.get("sent_s") is not None]


def slo_share(records: Iterable[Dict], seconds: float, ttft_limit_ms: float,
              mean_gap_limit_ms: float) -> Optional[float]:
    """% of requests due in the window that met both limits; a failed
    request misses."""
    due = due_in_window(records, seconds)
    if not due:
        return None
    met = 0
    for r in due:
        ts = r["token_s"]
        if not r["ok"] or not ts:
            continue
        first = (ts[0] - r["due_s"]) * 1e3
        gap = ((ts[-1] - ts[0]) / (len(ts) - 1) * 1e3) if len(ts) > 1 else 0.0
        met += first <= ttft_limit_ms and gap <= mean_gap_limit_ms
    return 100.0 * met / len(due)


def longest_silence_ms(records: Iterable[Dict], seconds: float) -> Optional[float]:
    """The longest stretch of the window in which no token of any stream
    arrived: a stall of the whole system shows here and nowhere else
    (one long gap per stream vanishes among a hundred thousand)."""
    ts = sorted(t for r in records for t in r["token_s"]
                if in_window(t, seconds))
    if len(ts) < 2:
        return None
    return max(b - a for a, b in zip(ts, ts[1:])) * 1e3
