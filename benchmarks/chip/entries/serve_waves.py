"""Adapter: closed-loop waves through ``ServingEngine.generate``.

The engine is built as ``repro.launch.serve.main`` builds it, from the
configuration file's ``model_config`` and the benchmark's seeded weights.
Each wave is ``batch`` requests that share a prompt length P and an output
length G (the engine decodes with one position for the whole batch), taken
in order from the traffic file's replay list; the next wave starts when
``generate`` returns. Prompt ids are drawn from the seed.

Clocks, all host ``perf_counter`` after the result is on the host:

* a request starts when its wave's ``generate`` is called;
* its first token is on the host when the adapter's wrapper around
  ``engine.prefill`` has blocked on the token prefill returns;
* its last token is on the host when ``generate`` returns;
* its j-th token (0-based) is taken to arrive at
  first + j * (last - first) / (G - 1): decode steps between the first and
  the last token are alike, and the engine hands no token over earlier.

The window opens at wave 0's start (``window_opens: wave_start``) or at
its first token (``first_token``: prefill lies outside the window) and
closes ``seconds`` later. The wave in flight at the close runs to its end.
Then the sampled requests are compared with the reference.

A traced run profiles the wave that the traffic file's ``trace`` names,
from its start or from its first token (``opens``), until it returns or
``seconds`` have passed.
"""
from __future__ import annotations

import os
import gc
import sys
import time

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import weights as W  # noqa: E402
from reference import dense_gqa  # noqa: E402

GIB = 2 ** 30
WARMUP = 2 ** 32 - 1   # the warm-up wave's index: no wave of a window has it


def program_params(cfg: dict, top: dict, layers: dict) -> dict:
    """The benchmark's weights, renamed into the program's parameter tree
    (no copy)."""
    emb = {"embedding": top["embedding"]}
    if "lm_head" in top:
        emb["lm_head"] = top["lm_head"]
    return {
        "emb": emb,
        "ln_f": {"scale": top["final_norm"]},
        "layers": {
            "ln1": {"scale": layers["attn_norm"]},
            "attn": {k: layers[k] for k in ("wq", "wk", "wv", "wo")},
            "ln2": {"scale": layers["ffn_norm"]},
            "ffn": {k: layers[k] for k in ("w_gate", "w_up", "w_down")},
        },
    }


def _same_layout(params, model) -> None:
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the program's parameter layout differs from the "
                           "benchmark's; the adapter needs a new mapping")


def prompts_for(seed: int, wave: int, batch: int, length: int, vocab: int):
    rng = np.random.default_rng([int(seed), wave])
    return rng.integers(0, vocab, (batch, length), dtype=np.int64).astype(np.int32)


def build(cell, seed: int):
    """The engine, its decode step compiled, holding the seed's weights."""
    from repro.configs.base import ModelConfig
    from repro.launch.serve import ServingEngine
    from repro.models import LanguageModel

    mc = cell.config["model_config"]
    traffic = cell.traffic
    model = LanguageModel(ModelConfig(**mc))
    top, layers = W.make(mc, seed)
    params = program_params(mc, top, layers)
    _same_layout(params, model)
    engine = ServingEngine(model, params, traffic["batch"], traffic["max_len"])
    engine.compile()
    return engine


def program_bytes(engine) -> int:
    m = engine.decode.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def run(cell, control: bool = False) -> dict:
    """One run of the cell: set-up, the window, then the comparison. The
    host mesh is set, as ``launch/serve.main`` sets it, for the run only."""
    from repro.launch.mesh import make_host_mesh

    with jax.sharding.set_mesh(make_host_mesh()):
        return _run(cell, control)


def _run(cell, control: bool) -> dict:
    traffic, mc, seed = cell.traffic, cell.config["model_config"], cell.seed
    batch, vocab = traffic["batch"], mc["vocab_size"]
    waves = traffic["waves"]     # replayed in order, repeated if need be
    if max(p + g for p, g in waves) > traffic["max_len"]:
        raise ValueError("a wave does not fit max_len")

    engine = build(cell, seed)
    # Warm every program the window runs: one short wave at the window's batch.
    engine.generate(prompts_for(seed, WARMUP, batch, 2, vocab), 2)
    dev = jax.devices()[0]
    cell.log(f"decode step program {program_bytes(engine) / GIB:.3f} GiB "
             f"per device (compiler); memory_stats peak before the window "
             f"{(dev.memory_stats() or {}).get('peak_bytes_in_use', 0) / GIB:.3f} GiB")

    first_times: list[float] = []
    prefill_spans: list[tuple[float, float, int]] = []
    spec = traffic["trace"] if cell.trace else None
    state = {"open": None, "wave": 0, "trace_t0": None}
    orig_prefill = engine.prefill

    def start_trace(opens, t):
        if (spec and spec["opens"] == opens and state["wave"] == spec["wave"]
                and state["trace_t0"] is None):
            state["trace_t0"] = t
            cell.tracer.start(spec["seconds"])

    def prefill(prompts):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.prefill"):
            tok = jax.block_until_ready(orig_prefill(prompts))
        t1 = time.perf_counter()
        first_times.append(t1)
        prefill_spans.append((t0, t1, prompts.size))
        if state["open"] is None and traffic["window_opens"] == "first_token":
            state["open"] = t1
        start_trace("first_token", t1)
        return tok

    engine.prefill = prefill
    # Set-up leaves many long-lived objects (JAX, the program, the compiled
    # steps); frozen, no collection in the window scans them. On a v5e host,
    # 4 of 12 chat runs without this had host pauses of 0.7-3 s in the
    # window, and none of 12 with it.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - cell.t_start

    records, outputs, prompts_kept = [], [], []
    k = 0
    while True:
        p, g = waves[k % len(waves)]
        prompts = prompts_for(seed, k, batch, p, vocab)
        state["wave"] = k
        t_start = time.perf_counter()
        if state["open"] is None and traffic["window_opens"] == "wave_start":
            state["open"] = t_start
        start_trace("wave_start", t_start)
        with jax.profiler.TraceAnnotation("engine.generate"):
            out = engine.generate(prompts, g)
        t_end = time.perf_counter()
        if spec and k == spec["wave"]:
            cell.tracer.stop()
        records.append({"wave": k, "P": p, "G": g, "B": batch, "start": t_start,
                        "first": first_times[-1], "last": t_end})
        outputs.append(out)
        prompts_kept.append(prompts)
        k += 1
        if t_end >= state["open"] + cell.seconds and (not spec or k > spec["wave"]):
            break
    if state["trace_t0"] is not None:
        cell.tracer.join()
    t_open = state["open"]
    for r in records:
        for key in ("start", "first", "last"):
            r[key] -= t_open
    prefill_spans = [(a - t_open, b - t_open, n) for a, b, n in prefill_spans]

    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    failed = sum(batch for o, r in zip(outputs, records)
                 if o.shape != (batch, r["G"]) or o.min() < 0 or o.max() >= vocab)
    sample = _sample(records, seed, batch, traffic["check_requests"])
    del engine, orig_prefill
    gc.unfreeze()
    gc.collect()
    numbers = compare(cell, records, prompts_kept, outputs, sample, control)

    trace = None
    if state["trace_t0"] is not None:
        t0 = time.perf_counter()
        trace = cell.tracer.result()
        cell.log(f"trace of wave {spec['wave']} reduced in "
                 f"{time.perf_counter() - t0:.1f}s")
        w = records[spec["wave"]]
        pos = w["P"] if spec["opens"] == "first_token" else 0
        trace["first_step"] = {"wave": spec["wave"], "pos": pos}
        shift = state["trace_t0"] - t_open   # host spans on the profile's clock
        spans = [("engine.generate", r["start"], r["last"]) for r in records]
        spans += [("engine.prefill", a, b) for a, b, _ in prefill_spans]
        trace["host"] = [[n, int((a - shift) * 1e9), int((b - a) * 1e9)]
                         for n, a, b in spans]
    return {"setup_s": setup_s, "window": (0.0, float(cell.seconds)),
            "records": records, "prefill": prefill_spans,
            "attempted": batch * len(records), "failed": int(failed),
            "memory_peak_bytes": peak, "numbers": numbers, "trace": trace}


def _sample(records, seed, batch, n):
    """(wave, row) pairs drawn from the seed among finished requests, with
    one request of the longest wave among them."""
    rng = np.random.default_rng([int(seed), 7])
    longest = max(range(len(records)),
                  key=lambda i: (records[i]["P"] + records[i]["G"], -i))
    pairs = [(longest, int(rng.integers(batch)))]
    pool = [(i, j) for i in range(len(records)) for j in range(batch)
            if (i, j) != pairs[0]]
    for idx in rng.choice(len(pool), size=min(n - 1, len(pool)), replace=False):
        pairs.append(pool[int(idx)])
    return pairs


def compare(cell, records, prompts, outputs, sample, control) -> dict:
    """The numbers compared: the widest logit gap, under the float32
    reference, of the served tokens of the sampled requests; with
    ``control``, also that of the fp8 control's first choices at the same
    positions (calibration only)."""
    mc = cell.config["model_config"]
    max_len = cell.traffic["max_len"]
    n_served = max(records[i]["G"] for i, _ in sample)
    tokens = np.zeros((len(sample), max_len), np.int32)
    rows = np.zeros((len(sample), n_served), np.int32)
    served = np.zeros((len(sample), n_served), np.int32)
    for s, (i, j) in enumerate(sample):
        p, g = records[i]["P"], records[i]["G"]
        seq = np.concatenate([prompts[i][j], outputs[i][j]])
        tokens[s, :p + g] = seq
        # token t of the output was chosen by the logits at position p - 1 + t;
        # short rows repeat their last pair, which changes no maximum.
        r = np.minimum(np.arange(n_served), g - 1)
        rows[s] = p - 1 + r
        served[s] = outputs[i][j][r]
    t0 = time.perf_counter()
    gaps = dense_gqa.served_gaps(mc, cell.seed, tokens, rows, served,
                                 control=control)
    cell.log(f"reference over {len(sample)} requests, "
             f"{sum(records[i]['G'] for i, _ in sample)} served tokens: "
             f"{time.perf_counter() - t0:.1f}s")
    numbers = {"max_logit_gap": float(gaps["gap"].max())}
    if control:
        numbers["control_max_logit_gap"] = float(gaps["control_gap"].max())
    return numbers

