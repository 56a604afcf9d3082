"""The arithmetic behind every reported number: quartiles, percentiles, the
median of block readings, per-request first-token time and gap."""

import statistics
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """``q`` in [0, 100], linear interpolation between order statistics (the
    rule of ``numpy.percentile``'s default); needs at least one value."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def beyond(values: Sequence[float], q: float) -> int:
    """How many samples lie strictly beyond the ``q``-th percentile."""
    cut = percentile(values, q)
    return sum(1 for v in values if v > cut)


def quartiles(values: Sequence[float]) -> List[float]:
    """First quartile, median, third quartile as ``statistics.quantiles``
    gives them (the driver's rule for a spread)."""
    if len(values) < 2:
        return [values[0]] * 3 if values else []
    return statistics.quantiles(values, n=4)


def spread(values: Sequence[float]) -> float:
    """Distance between the quartiles as a share of the median."""
    q1, q2, q3 = quartiles(values)
    return (q3 - q1) / q2


def block_readings(ready_times: Sequence[float], tokens_per_block: int,
                   chips: int) -> List[float]:
    """Tokens per second per chip of each block: ``ready_times[0]`` is when
    the block before the window became ready; reading ``i`` spans the time
    from block ``i-1`` ready to block ``i`` ready."""
    return [tokens_per_block / (b - a) / chips
            for a, b in zip(ready_times, ready_times[1:])]


def request_times(submitted: float, first_token_at: float, last_token_at: float,
                  tokens: int) -> Dict[str, Optional[float]]:
    """Per-request first-token time and gap between tokens, in ms. The gap
    exists only for a request of two tokens or more."""
    tpot = None
    if tokens >= 2:
        tpot = (last_token_at - first_token_at) / (tokens - 1) * 1e3
    return {"ttft_ms": (first_token_at - submitted) * 1e3, "tpot_ms": tpot}


def note_delivery(record: dict, tokens_now: int, first_token_at, now: float) -> None:
    """Keep, for one request, the time between deliveries of tokens to its
    stream: called after every ``step()`` with the tokens the request holds.
    The first delivery is measured from the first token's own time; a step
    that brought only the first token is no delivery."""
    if tokens_now <= max(record["delivered"], 1):
        record["delivered"] = max(record["delivered"], tokens_now)
        return
    since = record["delivered_at"] if record["delivered_at"] is not None \
        else first_token_at
    record["delivery_gaps"].append(now - since)
    record["delivered"], record["delivered_at"] = tokens_now, now
