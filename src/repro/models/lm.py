"""Model assembly: specs, init, forward (scan over layers), loss, and the
prefill/decode paths with layer-stacked caches.

One entry point serves all 10 assigned architectures:

    model = LanguageModel(cfg)
    params = model.init(key)
    h = model.forward(params, batch)          # train/prefill hidden states
    loss = model.loss(params, batch)
    cache = model.init_cache(batch_size, max_len)
    logits, cache = model.decode_step(params, cache, tokens, pos)

Layer stacks are scanned (``lax.scan`` over stacked params) so the HLO stays
compact at 94 layers; heterogeneous stacks (DeepSeek first-k-dense, Zamba
shared block) mix one unrolled group with a scanned group.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks
from repro.models.base import Specs, axes_tree, init_params, stack_specs
from repro.sharding.partition import sp_boundary
from repro.models.layers import (chunked_cross_entropy, embed, embedding_specs,
                                 logits_for_tokens, rmsnorm, rmsnorm_specs)

REMAT_POLICIES = {
    "none": None,
    "dots": lambda: jax.checkpoint_policies.checkpoint_dots,
    "full": lambda: jax.checkpoint_policies.nothing_saveable,
}

# The decode step's new key and value rows leave the layer scan, and are
# written into the cache, widened to float32. XLA:CPU has no bf16
# dynamic-update-slice: it widens one itself, behind conversions that carry no
# op metadata, and the decode step's scope map (benchmarks/chip/scopes.py)
# would then place the writes by an operand instead of by their scope. Widened
# here, the writes keep their scopes on every backend. The values are exact in
# float32, and XLA:TPU folds the round trip into a write in the cache's own
# dtype, in place.
ROW_DTYPE = jnp.float32


def _maybe_remat(fn, remat: str):
    if remat == "none":
        return fn
    policy = REMAT_POLICIES[remat]()
    return jax.checkpoint(fn, policy=policy)


@dataclass
class LanguageModel:
    cfg: ModelConfig
    impl: str = "chunked"       # sdpa implementation
    remat: str = "none"

    # ------------------------------------------------------------------ specs --
    def specs(self) -> Specs:
        cfg = self.cfg
        s: Specs = {
            "emb": embedding_specs(cfg.vocab_size, cfg.d_model, cfg.tie_embeddings),
            "ln_f": rmsnorm_specs(cfg.d_model),
        }
        if cfg.family in ("dense", "vlm"):
            s["layers"] = stack_specs(blocks.dense_block_specs(cfg), cfg.n_layers)
        elif cfg.family == "moe":
            kd = cfg.first_k_dense
            if kd:
                s["dense_layers"] = stack_specs(
                    blocks.moe_block_specs(cfg, dense_ffn=True), kd)
            s["layers"] = stack_specs(
                blocks.moe_block_specs(cfg, dense_ffn=False), cfg.n_layers - kd)
        elif cfg.family == "ssm":
            s["layers"] = stack_specs(blocks.mamba_block_specs(cfg), cfg.n_layers)
        elif cfg.family == "hybrid":
            s["layers"] = stack_specs(blocks.mamba_block_specs(cfg), cfg.n_layers)
            s["shared_attn"] = blocks.shared_attn_block_specs(cfg)
        elif cfg.family == "audio":
            s["enc_layers"] = stack_specs(
                blocks.encoder_block_specs(cfg), cfg.n_encoder_layers)
            s["layers"] = stack_specs(
                blocks.decoder_block_specs(cfg), cfg.n_layers)
            s["ln_enc"] = rmsnorm_specs(cfg.d_model)
        else:
            raise ValueError(cfg.family)
        return s

    def init(self, key, dtype=jnp.bfloat16):
        return init_params(self.specs(), key, dtype)

    def axes(self):
        return axes_tree(self.specs())

    # ------------------------------------------------------------- embeddings --
    def _embed_inputs(self, params, batch):
        """Handles token-only, VLM (patch embeds + tokens) and audio
        (encoder frames + decoder tokens) input conventions."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = embed(params["emb"], tokens)
        if cfg.frontend == "vision" and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(x.dtype)
            x = jnp.concatenate([pe, x[:, pe.shape[1]:]], axis=1)
        return x

    # ---------------------------------------------------------------- forward --
    def forward(self, params, batch):
        """Returns (hidden (B,S,d), aux_loss)."""
        cfg = self.cfg
        if cfg.family == "audio":
            return self._forward_audio(params, batch)
        x = self._embed_inputs(params, batch)
        b, s = x.shape[:2]
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        aux = jnp.zeros((), jnp.float32)

        if cfg.family in ("dense", "vlm"):
            body = _maybe_remat(
                lambda x_, p_: sp_boundary(
                    blocks.dense_block(p_, cfg, sp_boundary(x_), positions,
                                       impl=self.impl)), self.remat)
            x, _ = jax.lax.scan(lambda c, p: (body(c, p), None),
                                x, params["layers"])
        elif cfg.family == "moe":
            def _moe_block(x_, p_):
                y, a = blocks.moe_block(p_, cfg, sp_boundary(x_), positions,
                                        impl=self.impl)
                return sp_boundary(y), a

            block = _maybe_remat(_moe_block, self.remat)

            def moe_body(carry, p):
                x_, aux_ = carry
                y, a = block(x_, p)
                return (y, aux_ + a), None

            if cfg.first_k_dense:
                (x, aux), _ = jax.lax.scan(moe_body, (x, aux),
                                           params["dense_layers"])
            (x, aux), _ = jax.lax.scan(moe_body, (x, aux), params["layers"])
        elif cfg.family == "ssm":
            body = _maybe_remat(
                lambda x_, p_: sp_boundary(
                    blocks.mamba_block(p_, cfg, sp_boundary(x_))), self.remat)
            x, _ = jax.lax.scan(lambda c, p: (body(c, p), None),
                                x, params["layers"])
        elif cfg.family == "hybrid":
            shared = params["shared_attn"]
            period = cfg.attn_every

            def hybrid_body(carry, inp):
                x_, i = carry
                p_ = inp
                x_ = sp_boundary(blocks.mamba_block(p_, cfg, sp_boundary(x_)))
                x_ = jax.lax.cond(
                    (i + 1) % period == 0,
                    lambda v: sp_boundary(blocks.shared_attn_block(
                        shared, cfg, v, positions, impl=self.impl)),
                    lambda v: v,
                    x_,
                )
                return (x_, i + 1), None

            (x, _), _ = jax.lax.scan(hybrid_body, (x, jnp.int32(0)),
                                     params["layers"])
        h = rmsnorm(params["ln_f"], x, cfg.norm_eps)
        return h, aux

    def _forward_audio(self, params, batch):
        cfg = self.cfg
        frames = batch["frames"]  # (B, S_enc, d) — stubbed conv frontend output
        b, s_enc, _ = frames.shape
        enc_pos = jnp.broadcast_to(jnp.arange(s_enc, dtype=jnp.int32), (b, s_enc))
        x = frames.astype(jnp.bfloat16)

        enc_block = _maybe_remat(
            lambda c, p: sp_boundary(
                blocks.encoder_block(p, cfg, sp_boundary(c), enc_pos,
                                     impl=self.impl)), self.remat)
        x, _ = jax.lax.scan(lambda c, p: (enc_block(c, p), None),
                            x, params["enc_layers"])
        enc_out = rmsnorm(params["ln_enc"], x, cfg.norm_eps)

        tokens = batch["tokens"]
        s_dec = tokens.shape[1]
        dec_pos = jnp.broadcast_to(jnp.arange(s_dec, dtype=jnp.int32), (b, s_dec))
        y = embed(params["emb"], tokens)

        dec_block = _maybe_remat(
            lambda c, p: sp_boundary(
                blocks.decoder_block(p, cfg, sp_boundary(c), enc_out, dec_pos,
                                     enc_pos, impl=self.impl)), self.remat)
        y, _ = jax.lax.scan(lambda c, p: (dec_block(c, p), None),
                            y, params["layers"])
        h = rmsnorm(params["ln_f"], y, cfg.norm_eps)
        return h, jnp.zeros((), jnp.float32)

    # ------------------------------------------------------------------- loss --
    def loss(self, params, batch, aux_weight: float = 0.01):
        h, aux = self.forward(params, batch)
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        ce = chunked_cross_entropy(params["emb"], h, labels, mask=mask)
        return ce + aux_weight * aux

    # ------------------------------------------------------------------ cache --
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   enc_len: int = 0):
        """Zeroed decode caches, stacked over layers. Attention caches are
        head-major, (layers, batch, kv_heads, positions, head_dim): each
        head's keys and values are one (positions, head_dim) matrix, the
        operand that the decode step's per-head matmuls read where it lies.
        (With positions before heads, a TPU copies every layer's slice to
        transpose it, at every step.)"""
        cfg = self.cfg
        L = cfg.n_layers
        if cfg.family in ("dense", "vlm"):
            if cfg.use_mla:
                return {
                    "ckv": jnp.zeros((L, batch, max_len, cfg.kv_lora_rank), dtype),
                    "krope": jnp.zeros((L, batch, max_len, cfg.rope_head_dim), dtype),
                }
            kvh, hd = cfg.n_kv_heads, cfg.head_dim
            return {
                "k": jnp.zeros((L, batch, kvh, max_len, hd), dtype),
                "v": jnp.zeros((L, batch, kvh, max_len, hd), dtype),
            }
        if cfg.family == "moe":
            kd = cfg.first_k_dense
            base = {}
            if cfg.use_mla:
                base["ckv"] = jnp.zeros((L, batch, max_len, cfg.kv_lora_rank), dtype)
                base["krope"] = jnp.zeros((L, batch, max_len, cfg.rope_head_dim), dtype)
            else:
                kvh, hd = cfg.n_kv_heads, cfg.head_dim
                base["k"] = jnp.zeros((L, batch, kvh, max_len, hd), dtype)
                base["v"] = jnp.zeros((L, batch, kvh, max_len, hd), dtype)
            return base
        if cfg.family == "ssm":
            return self._ssm_cache(batch, dtype)
        if cfg.family == "hybrid":
            cache = self._ssm_cache(batch, dtype)
            n_inv = cfg.n_layers // cfg.attn_every
            kvh, hd = cfg.n_kv_heads, cfg.head_dim
            cache["shared_k"] = jnp.zeros((n_inv, batch, kvh, max_len, hd), dtype)
            cache["shared_v"] = jnp.zeros((n_inv, batch, kvh, max_len, hd), dtype)
            return cache
        if cfg.family == "audio":
            kvh, hd = cfg.n_kv_heads, cfg.head_dim
            return {
                "k": jnp.zeros((L, batch, kvh, max_len, hd), dtype),
                "v": jnp.zeros((L, batch, kvh, max_len, hd), dtype),
                "cross_k": jnp.zeros((L, batch, kvh, enc_len, hd), dtype),
                "cross_v": jnp.zeros((L, batch, kvh, enc_len, hd), dtype),
            }
        raise ValueError(cfg.family)

    def _ssm_cache(self, batch: int, dtype):
        cfg = self.cfg
        conv_ch = cfg.d_inner + 2 * cfg.ssm_state
        return {
            "conv": jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv - 1, conv_ch), dtype),
            "ssm": jnp.zeros((cfg.n_layers, batch, cfg.ssm_heads,
                              cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
        }

    # ------------------------------------------------------------ decode step --
    def decode_step(self, params, cache, tokens, pos):
        """tokens: (B,1) int32; pos: scalar int32 (current length).
        Returns (logits (B,1,V), new_cache).

        Named scopes give each part of the step an owner in the compiled
        program's op metadata, and so in a device profile: ``embed``;
        ``layers``, the scan over the layer stack, and inside it
        ``attention`` (which reads the stacked KV cache where it lies) and
        ``ffn``, then ``cache_update``, the one write of every layer's new
        key and value rows at ``pos`` after the scan; ``logits_sample``, the
        final norm and head (and the sampling in ``serve/step.py``)."""
        with jax.named_scope("embed"):
            x = embed(params["emb"], tokens)
        with jax.named_scope("layers"):
            x, cache = self._decode_layers(params, cache, x, pos)
        with jax.named_scope("logits_sample"):
            h = rmsnorm(params["ln_f"], x, self.cfg.norm_eps)
            return logits_for_tokens(params["emb"], h), cache

    def _decode_layers(self, params, cache, x, pos):
        cfg = self.cfg
        b = x.shape[0]

        if cfg.family in ("dense", "vlm", "moe"):
            if cfg.use_mla:
                from repro.models.attention import mla_decode
                from repro.models.layers import ffn as ffn_fn
                from repro.models.moe import moe_ffn

                def body(x_, xs):
                    p_, i = xs
                    ckv = jax.lax.dynamic_index_in_dim(cache["ckv"], i, 0, False)
                    krope = jax.lax.dynamic_index_in_dim(cache["krope"], i, 0, False)
                    with jax.named_scope("attention"):
                        h = rmsnorm(p_["ln1"], x_, cfg.norm_eps)
                        o, c_row, k_row = mla_decode(p_["attn"], cfg, h, ckv, krope, pos)
                        x_ = x_ + o
                    with jax.named_scope("ffn"):
                        h = rmsnorm(p_["ln2"], x_, cfg.norm_eps)
                        if "ffn" in p_:
                            x_ = x_ + ffn_fn(p_["ffn"], h)
                        else:
                            x_ = x_ + moe_ffn(p_["moe"], cfg, h)[0]
                    return x_, (c_row.astype(ROW_DTYPE), k_row.astype(ROW_DTYPE))

                # The leading dense layers and the MoE layers are two scans
                # over one stacked latent cache; each reads its layers'
                # slices where they lie, by index.
                kd = cfg.first_k_dense
                rows = []
                for pkey, lo, hi in (("dense_layers", 0, kd),
                                     ("layers", kd, cfg.n_layers)):
                    if hi > lo:
                        x, r = jax.lax.scan(
                            body, x, (params[pkey], jnp.arange(lo, hi)))
                        rows.append(r)
                cache = _write_rows(cache, {
                    n: jnp.concatenate([r[j] for r in rows])
                    for j, n in enumerate(("ckv", "krope"))}, pos)
            else:
                from repro.models.attention import gqa_decode
                from repro.models.layers import ffn as ffn_fn

                def body(x_, xs):
                    p_, k_, v_ = xs
                    with jax.named_scope("attention"):
                        h = rmsnorm(p_["ln1"], x_, cfg.norm_eps)
                        o, k_row, v_row = gqa_decode(p_["attn"], cfg, h, k_, v_, pos)
                        x_ = x_ + o
                    with jax.named_scope("ffn"):
                        h = rmsnorm(p_["ln2"], x_, cfg.norm_eps)
                        if "ffn" in p_:
                            x_ = x_ + ffn_fn(p_["ffn"], h)
                        else:
                            from repro.models.moe import moe_ffn
                            y, _ = moe_ffn(p_["moe"], cfg, h)
                            x_ = x_ + y
                    return x_, (k_row.astype(ROW_DTYPE), v_row.astype(ROW_DTYPE))

                x, (k_rows, v_rows) = jax.lax.scan(
                    body, x, (params["layers"], cache["k"], cache["v"]))
                cache = _write_rows(cache, {"k": k_rows, "v": v_rows}, pos)
        elif cfg.family == "ssm":
            def body(x_, xs):
                p_, cs, ss = xs
                x_, cs, ss = blocks.mamba_block_decode(p_, cfg, x_, cs, ss)
                return x_, (cs, ss)

            x, (conv_new, ssm_new) = jax.lax.scan(
                body, x, (params["layers"], cache["conv"], cache["ssm"]))
            cache = {"conv": conv_new, "ssm": ssm_new}
        elif cfg.family == "hybrid":
            shared = params["shared_attn"]
            period = cfg.attn_every
            sk, sv = cache["shared_k"], cache["shared_v"]

            def body(carry, xs):
                x_, i, sk_, sv_ = carry
                p_, cs, ss = xs
                x_, cs, ss = blocks.mamba_block_decode(p_, cfg, x_, cs, ss)

                def do_shared(args):
                    x_in, sk_in, sv_in = args
                    inv = i // period
                    from repro.models.attention import gqa_decode
                    from repro.models.layers import ffn as ffn_fn
                    k_i = jax.lax.dynamic_index_in_dim(sk_in, inv, 0, keepdims=False)
                    v_i = jax.lax.dynamic_index_in_dim(sv_in, inv, 0, keepdims=False)
                    with jax.named_scope("attention"):
                        h = rmsnorm(shared["ln1"], x_in, cfg.norm_eps)
                        o, k_row, v_row = gqa_decode(shared["attn"], cfg, h, k_i, v_i, pos)
                        x2 = x_in + o
                    with jax.named_scope("ffn"):
                        h = rmsnorm(shared["ln2"], x2, cfg.norm_eps)
                        x2 = x2 + ffn_fn(shared["ffn"], h)
                    with jax.named_scope("cache_update"):
                        at = (inv, 0, 0, pos, 0)
                        sk2 = jax.lax.dynamic_update_slice(sk_in, k_row[None], at)
                        sv2 = jax.lax.dynamic_update_slice(sv_in, v_row[None], at)
                    return x2, sk2, sv2

                x_, sk_, sv_ = jax.lax.cond(
                    (i + 1) % period == 0, do_shared,
                    lambda a: a, (x_, sk_, sv_))
                return (x_, i + 1, sk_, sv_), (cs, ss)

            (x, _, sk, sv), (conv_new, ssm_new) = jax.lax.scan(
                body, (x, jnp.int32(0), sk, sv),
                (params["layers"], cache["conv"], cache["ssm"]))
            cache = {"conv": conv_new, "ssm": ssm_new,
                     "shared_k": sk, "shared_v": sv}
        elif cfg.family == "audio":
            from repro.models.attention import decode_attention, gqa_decode
            from repro.models.layers import ffn as ffn_fn

            def body(x_, xs):
                p_, k_, v_, ck, cv = xs
                with jax.named_scope("attention"):
                    h = rmsnorm(p_["ln1"], x_, cfg.norm_eps)
                    o, k_row, v_row = gqa_decode(p_["attn"], cfg, h, k_, v_, pos)
                    x_ = x_ + o
                    h = rmsnorm(p_["ln_cross"], x_, cfg.norm_eps)
                    q = jnp.einsum("bsd,de->bse", h, p_["cross"]["wq"]).reshape(
                        b, 1, cfg.n_heads, cfg.head_dim)
                    o = decode_attention(q, ck, cv, kv_len=ck.shape[2])
                    x_ = x_ + jnp.einsum("bse,ed->bsd", o.reshape(b, 1, -1),
                                         p_["cross"]["wo"])
                with jax.named_scope("ffn"):
                    h = rmsnorm(p_["ln2"], x_, cfg.norm_eps)
                    x_ = x_ + ffn_fn(p_["ffn"], h)
                return x_, (k_row.astype(ROW_DTYPE), v_row.astype(ROW_DTYPE))

            x, (k_rows, v_rows) = jax.lax.scan(
                body, x, (params["layers"], cache["k"], cache["v"],
                          cache["cross_k"], cache["cross_v"]))
            cache = _write_rows(cache, {"k": k_rows, "v": v_rows}, pos)
        else:
            raise ValueError(cfg.family)
        return x, cache


def _write_rows(cache, rows, pos):
    """Write every layer's new rows, stacked by the layer scan, into the
    stacked cache at ``pos``: one write per cache array (positions on its
    second-last axis: K and V (L, B, KVH, S, D), the latent caches (L, B,
    S, r)), in place on a donated cache, after the scan has read it."""
    with jax.named_scope("cache_update"):
        out = dict(cache)
        for n, r in rows.items():
            at = (0,) * (r.ndim - 2) + (pos, 0)
            out[n] = jax.lax.dynamic_update_slice(
                cache[n].astype(ROW_DTYPE), r, at).astype(cache[n].dtype)
        return out
