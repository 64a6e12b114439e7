"""Serving steps: prefill (builds the KV cache) and decode (one token).

``serve_step`` for the dry-run grid is the decode step: one new token
against a ``seq_len``-deep cache. Sampling is greedy/temperature/top-k on
fp32 logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_prefill_step(model):
    cfg = model.cfg

    def prefill(params, batch):
        """Runs the full-sequence forward and returns (last_logits, hidden).
        Cache population for the generic path is handled by running the
        chunked forward; serving engines that need the cache use
        ``decode_from_scratch`` below or keep prompt-parallel caches."""
        h, _ = model.forward(params, batch)
        from repro.models.layers import logits_for_tokens

        return logits_for_tokens(params["emb"], h[:, -1:, :])

    return prefill


def make_decode_step(model, sample: str = "greedy", temperature: float = 1.0,
                     top_k: int = 0):
    def decode_step(params, cache, tokens, pos, rng):
        logits, cache = model.decode_step(params, cache, tokens, pos)
        with jax.named_scope("logits_sample"):
            logits = logits[:, -1, :].astype(jnp.float32)
            if sample == "greedy":
                nxt = jnp.argmax(logits, axis=-1)
            else:
                logits = logits / jnp.maximum(temperature, 1e-6)
                if top_k:
                    vals, _ = jax.lax.top_k(logits, top_k)
                    logits = jnp.where(logits < vals[:, -1:], -1e30, logits)
                nxt = jax.random.categorical(rng, logits, axis=-1)
            return nxt[:, None].astype(jnp.int32), cache

    return decode_step
