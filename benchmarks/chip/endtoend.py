"""End-to-end metrics of a serving run, from the adapter's host clocks.

``run["records"]`` holds one entry per wave of ``B`` requests: prompt and
output lengths ``P``, ``G`` and the seconds, from the window's opening, at
which the wave started (``start``), its first token was on the host
(``first``) and its last (``last``). The window is ``run["window"]``,
(open, close].

The window's waves are those that started before its close and ended after
its opening. Each runs to its end, so the metrics count their requests
whole: the close decides only which waves count, and every metric follows
the step time continuously. Every function returns None where the window
holds no sample.
"""
from __future__ import annotations

import numpy as np


def window_waves(run: dict) -> list[dict]:
    lo, hi = run["window"]
    return [r for r in run["records"] if r["start"] < hi and r["last"] > lo]


def output_tok_s(run: dict) -> float | None:
    """Generated tokens of the window's waves that reached the host after
    the opening, over the seconds from the opening to the last one's end.
    Where the first token of a wave opens the window, that token is not
    counted."""
    lo = run["window"][0]
    waves = window_waves(run)
    n = sum(r["B"] * (r["G"] if r["first"] > lo else r["G"] - 1) for r in waves)
    return n / (max(r["last"] for r in waves) - lo) if n else None


def _p95(samples: list[float]) -> float | None:
    return float(np.percentile(samples, 95)) if samples else None


def ttft_p95_ms(run: dict) -> float | None:
    """p95, over the requests of the window's waves whose first token came
    after the opening, of first token minus the request's start."""
    lo = run["window"][0]
    s = [1e3 * (r["first"] - r["start"]) for r in window_waves(run)
         for _ in range(r["B"]) if r["first"] > lo]
    return _p95(s)


def tpot_p95_ms(run: dict) -> float | None:
    """p95, over the requests of the window's waves, of
    (last - first) / (G - 1)."""
    s = [1e3 * (r["last"] - r["first"]) / (r["G"] - 1) for r in window_waves(run)
         for _ in range(r["B"]) if r["G"] > 1]
    return _p95(s)


def setup_s(run: dict) -> float:
    """Seconds from the process's start to the window's opening work:
    imports, weights, engine, compile (or cache load) and warm-up."""
    return run["setup_s"]
