"""Device time of the decode step per named scope.

The program names the parts of its decode step with ``jax.named_scope``
(``models/lm.py``, ``models/attention.py``, ``serve/step.py``), and XLA
keeps each instruction's scope path in its op metadata (``op_name``, e.g.
``jit(decode_step)/layers/while/body/closed_call/ffn/dot_general``). The
reduced trace keeps only each op's instruction name, so the map from name to
path is read from the decode step's HLO text: ``decode_hlo`` lowers and
compiles the step as ``ServingEngine.compile`` does, and the compiler names
the instructions of the same program the same way each time (a test checks
that the two maps agree). An instruction the compiler added with no
metadata, such as the copy of a loop's result, takes the path of its first
operand that has one: the data it moves belongs there.

An op counts for a scope when the scope is the innermost of ``SCOPES`` on
its path and the op runs inside a run of the decode-step program; ``other``
is the rest of the program's time (``embed``, ops under no scope, gaps
between ops), so the six parts sum to the program's mean time.
"""
from __future__ import annotations

import functools
import json
import re

import devtrace

# scope name in the program -> part of the decode step
SCOPES = {"attention": "attention", "cache_update": "cache_update",
          "layers": "layer_loop", "ffn": "ffn", "logits_sample": "logits_sample"}
PARTS = tuple(SCOPES.values()) + ("other",)

INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = (.*)$")
OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
OPERAND = re.compile(r"%([\w.-]+)")


def op_paths(hlo: str) -> dict[str, str]:
    """Instruction name -> scope path, for every instruction of the module
    (an empty path where neither it nor an operand has metadata)."""
    paths: dict[str, str] = {}
    for line in hlo.splitlines():
        m = INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        found = OP_NAME.search(rest)
        if found:
            paths[name] = found.group(1)
            continue
        args = rest.split(", metadata=", 1)[0]
        paths[name] = next((paths[a] for a in OPERAND.findall(args)
                            if paths.get(a)), "")
    return paths


def part_of(path: str) -> str:
    """The part of the decode step that the innermost scope names."""
    for scope in reversed(path.split("/")):
        if scope in SCOPES:
            return SCOPES[scope]
    return "other"


@functools.cache
def _decode_hlo(model_config: str, batch: int, max_len: int) -> str:
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import LanguageModel
    from repro.serve.step import make_decode_step

    model = LanguageModel(ModelConfig(**json.loads(model_config)))
    with jax.sharding.set_mesh(make_host_mesh()):
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        cache = jax.eval_shape(lambda: model.init_cache(batch, max_len))
        tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        step = jax.jit(make_decode_step(model), donate_argnums=(1,))
        return step.lower(params, cache, tokens, jnp.int32(0),
                          jax.random.PRNGKey(0)).compile().as_text()


def decode_hlo(cell) -> str:
    """HLO text of the cell's decode step, compiled as the engine compiles it."""
    return _decode_hlo(json.dumps(cell.config["model_config"], sort_keys=True),
                       cell.traffic["batch"], cell.traffic["max_len"])


def part_ms(trace: dict, paths: dict[str, str]) -> dict[str, float] | None:
    """Device ms per decode-step run of each part, on the first chip. None
    where no op of a run falls under any scope (a program without them),
    or where an op of a run is not in ``paths`` (another program)."""
    runs = devtrace.program_runs(trace, "decode_step")
    if not runs:
        return None
    first = trace["devices"][sorted(trace["devices"])[0]]
    ops = sorted(first.get("XLA Ops", []), key=lambda ev: ev[1])
    ns = dict.fromkeys(PARTS, 0.0)
    i = 0
    for _, start, dur in runs:
        while i < len(ops) and ops[i][1] < start:
            i += 1
        while i < len(ops) and ops[i][1] < start + dur:
            name, _, d = ops[i]
            if name not in paths:
                return None
            ns[part_of(paths[name])] += d
            i += 1
    if not any(ns[p] for p in SCOPES.values()):
        return None
    step_ns = sum(d for _, _, d in runs)
    ns["other"] = step_ns - sum(ns[p] for p in SCOPES.values())
    return {p: v / len(runs) / 1e6 for p, v in ns.items()}


def read(run, cell, part: str) -> float | None:
    """One part's ms per decode-step run in the traced window."""
    parts = part_ms(run["trace"], op_paths(decode_hlo(cell)))
    return None if parts is None else parts[part]
