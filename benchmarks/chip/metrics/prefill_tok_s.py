"""Prompt tokens over host seconds inside ``engine.prefill``, for the
prefills that began and ended in the window (host clock, so it reads the
same with the profiler off)."""


def read(run, cell):
    lo, hi = run["window"]
    spans = [(a, b, n) for a, b, n in run["prefill"] if a >= lo and b <= hi]
    return sum(n for *_, n in spans) / sum(b - a for a, b, _ in spans) if spans else None
