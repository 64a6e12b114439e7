"""Batched serving driver: prefill a batch of prompts, then decode.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b-smoke \
        --batch 4 --prompt-len 32 --gen 16

Demonstrates the serving substrate: KV-cache allocation + sharding,
prefill-via-decode warmup, batched greedy/sampled decode with per-request
stop handling, and simple continuous-batching slot reuse.

``--sim`` switches to the analytic request-level simulator instead of the
jax model: Poisson arrivals against one simulated instance per COPA config
of an MLPerf serving scenario (``--bench``), reporting latency percentiles
and SLO goodput (see ``repro.serve.sim`` / ``repro.serve.fleet``):

    PYTHONPATH=src python -m repro.launch.serve --sim --bench resnet
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.launch.mesh import make_host_mesh
from repro.launch.runtime import describe_devices, enable_compile_cache
from repro.models import LanguageModel
from repro.serve.step import make_decode_step


# Emitted by JAX for every program it compiles or loads from the compile cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _mark_compile(event: str, duration_secs: float, **_) -> None:
    """A ``serve.compile`` marker, a span of a few microseconds that holds
    the compile's ``seconds``, in the profile if one runs."""
    if event == COMPILE_EVENT:
        with jax.profiler.TraceAnnotation("serve.compile", seconds=duration_secs):
            pass


@functools.cache
def _listen_for_compiles() -> None:
    """Registers ``_mark_compile`` once per process. The listener holds no
    engine, so it keeps none alive."""
    jax.monitoring.register_event_duration_secs_listener(_mark_compile)


class ServingEngine:
    """Minimal continuous-batching engine over the decode step.

    Under a running ``jax.profiler`` trace it writes host spans on the
    profile's clock: ``serve.generate`` and ``serve.prefill`` around those
    calls; one ``serve.step`` (``step_num``, ``pos``) per decode-step call,
    holding ``serve.dispatch`` (building the position and key, and the
    call) and, in generation, ``serve.fetch`` (the copy of the token to the
    host, where the host waits on the device); and a ``serve.compile``
    marker (``seconds``) for each program compiled or loaded from the
    compile cache after ``compile`` returned.
    """

    def __init__(self, model: LanguageModel, params, batch: int,
                 max_len: int, enc_len: int = 64):
        self.model = model
        self.params = params
        self.batch = batch
        self.max_len = max_len
        self.cache = model.init_cache(batch, max_len, enc_len=enc_len)
        self.decode = jax.jit(make_decode_step(model), donate_argnums=(1,))
        self.lengths = np.zeros(batch, np.int32)
        self.steps = 0          # decode-step calls over the engine's life

    def compile(self) -> float:
        """Compile the decode step for this engine's shapes before the first
        request, so that no request waits on it; returns the seconds."""
        t0 = time.perf_counter()
        tokens = jax.ShapeDtypeStruct((self.batch, 1), jnp.int32)
        self.decode = self.decode.lower(
            self.params, self.cache, tokens, jnp.int32(0),
            jax.random.PRNGKey(0)).compile()
        _listen_for_compiles()
        return time.perf_counter() - t0

    @contextlib.contextmanager
    def _step(self, pos: int):
        with jax.profiler.StepTraceAnnotation("serve.step",
                                              step_num=self.steps, pos=pos):
            yield
        self.steps += 1

    def _dispatch(self, tokens, pos: int, seed: int):
        """Enqueue one decode step; its tokens stay on the device."""
        with jax.profiler.TraceAnnotation("serve.dispatch"):
            toks, self.cache = self.decode(
                self.params, self.cache, tokens, jnp.int32(pos),
                jax.random.PRNGKey(seed))
        return toks

    @staticmethod
    def _fetch(toks) -> np.ndarray:
        with jax.profiler.TraceAnnotation("serve.fetch"):
            return np.asarray(toks)

    def prefill(self, prompts: np.ndarray):
        """Teacher-forced prefill via the decode step (token at a time —
        simple and exact; production prefill uses the chunked forward)."""
        b, plen = prompts.shape
        toks = None
        with jax.profiler.TraceAnnotation("serve.prefill"):
            for t in range(plen):
                with self._step(t):
                    toks = self._dispatch(prompts[:, t:t + 1], t, t)
        self.lengths[:] = plen
        return toks

    def generate(self, prompts: np.ndarray, steps: int):
        with jax.profiler.TraceAnnotation("serve.generate"):
            next_tok = self.prefill(prompts)
            out = [self._fetch(next_tok)]
            pos = prompts.shape[1]
            for i in range(steps - 1):
                with self._step(pos + i):
                    next_tok = self._dispatch(next_tok, pos + i, 1000 + i)
                    out.append(self._fetch(next_tok))
        self.lengths += steps
        return np.concatenate(out, axis=1)


def sim_main(args):
    """Analytic serving simulation of one MLPerf bench across COPA configs."""
    from repro.core import copa
    from repro.core.sweep import serve_cost_grids
    from repro.serve.fleet import latency_goodput_rows
    from repro.serve.sim import ArrivalSpec, Slo

    cfgs = [copa.TABLE_V_BY_NAME[n] for n in args.sim_configs.split(",")]
    grids = serve_cost_grids(args.bench, cfgs)
    base = next(iter(grids.values()))
    sat = base.saturated_rps()
    rates = [f * sat for f in (0.5, 0.8, 1.1)]
    arrivals = ArrivalSpec(name=f"launch.{args.bench}", rate=sat,
                           n_requests=args.requests)
    slo = Slo(ttft_s=4 * base.step_time(base.max_batch), percentile=95)
    rows = latency_goodput_rows(grids, arrivals, rates, slo,
                                n_instances=args.instances, seed=0)
    print(f"{args.bench}: {args.instances} instance(s)/config, "
          f"SLO p95 TTFT<={slo.ttft_s*1e3:.2f}ms")
    for r in rows:
        print(f"{r['config']:<12} rate={r['rate_rps']:>9.1f}/s "
              f"ttft p50/p99 {r['ttft_p50_ms']:.2f}/{r['ttft_p99_ms']:.2f}ms "
              f"goodput {r['goodput_rps']:.1f}/s "
              f"{'ok' if r['slo_met'] else 'SLO MISS'}")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b-smoke")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--sim", action="store_true",
                    help="run the analytic request-level simulator instead "
                         "of the jax model")
    ap.add_argument("--bench", default="resnet",
                    help="[--sim] MLPerf serving bench (serve.mlperf.<bench>)")
    ap.add_argument("--sim-configs", default="GPU-N,HBM+L3",
                    help="[--sim] comma-separated Table-V config names")
    ap.add_argument("--instances", type=int, default=1,
                    help="[--sim] fleet size per config")
    ap.add_argument("--requests", type=int, default=2000)
    args = ap.parse_args(argv)

    if args.sim:
        return sim_main(args)

    enable_compile_cache()
    describe_devices("serve")
    cfg = configs.get(args.arch)
    jax.sharding.set_mesh(make_host_mesh())
    model = LanguageModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, args.batch, args.max_len)
    compile_s = engine.compile()
    mem = engine.decode.memory_analysis()
    print(f"[serve] {cfg.name}: decode step compiled in {compile_s:.2f}s; "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB arguments + "
          f"{mem.temp_size_in_bytes / 2**30:.2f} GiB temporaries", flush=True)

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    # Prefill ends where generate first waits on the device: its first
    # token on the host. Decode is the rest; each token is copied to the host.
    prefill, t_first = engine.prefill, []

    def timed_prefill(p):
        tok = jax.block_until_ready(prefill(p))
        t_first.append(time.perf_counter())
        return tok

    engine.prefill = timed_prefill
    t0 = time.perf_counter()
    toks = engine.generate(prompts, args.gen)
    t_end = time.perf_counter()
    pre_s, dec_s = t_first[0] - t0, t_end - t_first[0]
    dec_tok = args.batch * (args.gen - 1)
    print(f"prefill: {args.batch * args.prompt_len} prompt tokens in "
          f"{pre_s:.2f}s ({args.batch * args.prompt_len / pre_s:.1f} tok/s); "
          f"decode: {dec_tok} more tokens in {dec_s:.2f}s "
          f"({dec_tok / dec_s if dec_tok else 0.0:.1f} tok/s); "
          f"compile excluded")
    print("sample:", toks[0][:12].tolist())
    return toks


if __name__ == "__main__":
    main()
