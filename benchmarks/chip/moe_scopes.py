"""Device time of the decode step's expert and latent-attention scopes.

The MoE layer (``models/moe.py``) names its parts ``moe_route`` (router and
group-limited top-k), ``moe_dispatch`` (sort by expert, gather of the rows,
and the weighted combine), ``moe_experts`` (the grouped matmuls over the
experts held) and ``shared_experts``, all inside the step's ``ffn`` scope;
``mla_decode`` (``models/attention.py``) names ``latent_core``, the two
contractions against the latent cache and their softmax, inside
``attention``. So ``decode_scope_ms.ffn`` and ``.attention`` still count
them; these parts split those two.

Ops are mapped to scope paths as ``scopes.py`` maps them, from the decode
step's HLO text; an op counts for the innermost of ``SCOPES`` on its path.
"""
from __future__ import annotations

import devtrace
import scopes

SCOPES = {"moe_route": "route", "moe_dispatch": "dispatch",
          "moe_experts": "experts", "shared_experts": "shared",
          "latent_core": "latent_core"}


def part_of(path: str) -> str | None:
    for scope in reversed(path.split("/")):
        if scope in SCOPES:
            return SCOPES[scope]
    return None


def part_ms(trace: dict, paths: dict[str, str]) -> dict[str, float] | None:
    """Device ms per decode-step run of each part, on the first chip; None
    where no op of a run falls under any of them (a program without these
    scopes) or an op of a run is not in ``paths`` (another program)."""
    runs = devtrace.program_runs(trace, "decode_step")
    if not runs:
        return None
    first = trace["devices"][sorted(trace["devices"])[0]]
    ops = sorted(first.get("XLA Ops", []), key=lambda ev: ev[1])
    ns = dict.fromkeys(SCOPES.values(), 0.0)
    i = 0
    for _, start, dur in runs:
        while i < len(ops) and ops[i][1] < start:
            i += 1
        while i < len(ops) and ops[i][1] < start + dur:
            name, _, d = ops[i]
            if name not in paths:
                return None
            part = part_of(paths[name])
            if part:
                ns[part] += d
            i += 1
    if not any(ns.values()):
        return None
    return {p: v / len(runs) / 1e6 for p, v in ns.items()}


def read(run, cell, part: str) -> float | None:
    """One part's ms per decode-step run in the traced window."""
    parts = part_ms(run["trace"], scopes.op_paths(scopes.decode_hlo(cell)))
    return None if parts is None else parts[part]
