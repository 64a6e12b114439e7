"""End-to-end metrics against counts made by hand on wave records."""
from __future__ import annotations

import pytest

import bench_helpers  # noqa: F401  (puts the paths in place)
import endtoend


def _wave(start, first, last, P=10, G=11, B=4):
    return {"P": P, "G": G, "B": B, "start": start, "first": first, "last": last}


# window (0, 10]: waves 0 and 1 end in it, wave 2 starts in it and ends
# after it, wave 3 starts after it and is not the window's
CHAT = {"window": (0.0, 10.0), "records": [
    _wave(0.0, 2.0, 4.0), _wave(4.0, 5.0, 7.0, G=5),
    _wave(7.0, 8.0, 12.0, G=21), _wave(12.0, 13.0, 14.0)]}

# window opens at wave 0's first token; the wave outlasts the window
LONG = {"window": (0.0, 10.0), "records": [_wave(-3.0, 0.0, 30.0, G=301)]}


def test_output_tok_s_runs_to_the_last_waves_end():
    assert endtoend.output_tok_s(CHAT) == pytest.approx(4 * (11 + 5 + 21) / 12.0)
    # the token that opens the window is not counted
    assert endtoend.output_tok_s(LONG) == pytest.approx(4 * 300 / 30.0)


@pytest.mark.parametrize("slower", [1.01, 1.05])
def test_output_tok_s_follows_the_step_time(slower):
    scaled = {"window": CHAT["window"], "records": [
        {**r, **{k: slower * r[k] for k in ("start", "first", "last")}}
        for r in CHAT["records"][:3]]}
    assert endtoend.output_tok_s(scaled) == pytest.approx(
        endtoend.output_tok_s(CHAT) / slower)


def test_ttft_counts_first_tokens_after_the_opening():
    # 12 requests: 4 each at 2, 1 and 1 s
    assert endtoend.ttft_p95_ms(CHAT) == pytest.approx(2000.0)
    assert endtoend.ttft_p95_ms(LONG) is None


def test_tpot_over_the_windows_waves():
    # per request: 200, 500 and 200 ms; p95 of 12 falls in the 500 ms wave
    assert endtoend.tpot_p95_ms(CHAT) == pytest.approx(500.0)
    assert endtoend.tpot_p95_ms(LONG) == pytest.approx(100.0)


def test_empty_window_reads_none():
    empty = {"window": (0.0, 1.0), "records": [_wave(2.0, 3.0, 4.0)]}
    assert endtoend.output_tok_s(empty) is None
    assert endtoend.ttft_p95_ms(empty) is None
    assert endtoend.tpot_p95_ms(empty) is None
