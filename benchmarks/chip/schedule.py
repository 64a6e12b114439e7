"""Positions of the decode steps that a traced window holds.

The serving engine runs its decode step once per position of a wave, in
order: positions 0 .. P - 1 feed the prompt, P .. P + G - 2 feed the
generated tokens but the last. A trace that starts at position
``first_step["pos"]`` of wave ``first_step["wave"]`` therefore holds the
steps that follow from there, wave after wave.
"""
from __future__ import annotations


def traced_positions(records: list[dict], first_step: dict, n: int) -> list[tuple[int, int]]:
    """(batch, position) of the first ``n`` decode steps from the trace's
    start; fewer where the records end first."""
    out = []
    wave, pos = first_step["wave"], first_step["pos"]
    while len(out) < n and wave < len(records):
        r = records[wave]
        if pos <= r["P"] + r["G"] - 2:
            out.append((r["B"], pos))
            pos += 1
        else:
            wave, pos = wave + 1, 0
    return out
