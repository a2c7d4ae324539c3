"""The little arithmetic the harness and the readers share."""

from __future__ import annotations

# After the window closes an answer still out is waited for this long: it
# is late, not wrong, and its latency counts the wait.
DRAIN_S = 60.0


class RunFault(Exception):
    """The run cannot give a result line; exit code 1."""


def judged(compared: dict) -> bool:
    """``correct``: every number compared that has a limit is within it.
    The one test for the program's numbers and, under ``--control 1``, for
    the control's and each planted fault's: those must come out False."""
    return all(c["limit"] is None or c["value"] <= c["limit"] for c in compared.values())


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile.  No sample is a fault of the run, never a
    NaN in the line."""
    if not values:
        raise RunFault("a percentile of no samples")
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def token_gaps_ms(results) -> list[float]:
    """Every gap between consecutive streamed tokens of every request, on
    the client's clock."""
    return [(b - a) * 1e3 for r in results for a, b in zip(r.token_times, r.token_times[1:])]


def capture_span(seconds: float) -> tuple[float, float]:
    """(offset from the window's start, length) of a traced run's capture:
    3 s that end half a second before the window closes.  Starting and
    stopping the profiler stalls the traced process for seconds; at the
    window's end that stall falls after the window's arrivals and steps (in
    its middle it tipped the chat replica into its queue-bound regime in two
    traced runs of four on the chip; PERF.md Findings, PR 23)."""
    length = min(3.0, max(seconds / 3.0, 0.2))
    return max(seconds - length - 0.5, 0.0), length
