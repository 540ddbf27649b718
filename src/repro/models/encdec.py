"""Encoder-decoder LM (SeamlessM4T backbone).

Encoder consumes precomputed modality embeddings (frontend stub per the
assignment). Decoder is causal self-attention + cross-attention over the
encoder output. Positions use additive sinusoidal embeddings (RoPE off),
matching the enc-dec lineage of the arch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import kv_cache as kvc
from repro.models import layers as L
from repro.models.lm import _lm_head, _remat, chunked_ce_loss


def init_enc_layer(rng, cfg, dtype):
    r = L.split_rngs(rng, 2)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": L.init_attention(r[0], cfg, dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "mlp": L.init_mlp(r[1], cfg.d_model, cfg.d_ff, dtype),
    }


def init_dec_layer(rng, cfg, dtype):
    r = L.split_rngs(rng, 3)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": L.init_attention(r[0], cfg, dtype),
        "ln_x": jnp.ones((cfg.d_model,), dtype),
        "xattn": L.init_attention(r[1], cfg, dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "mlp": L.init_mlp(r[2], cfg.d_model, cfg.d_ff, dtype),
    }


def init_params(rng, cfg):
    dtype = jnp.dtype(cfg.param_dtype)
    r_embed, r_enc, r_dec, r_head = jax.random.split(rng, 4)
    enc_rngs = jax.random.split(r_enc, cfg.num_encoder_layers)
    dec_rngs = jax.random.split(r_dec, cfg.num_layers)
    enc = [init_enc_layer(r, cfg, dtype) for r in enc_rngs]
    dec = [init_dec_layer(r, cfg, dtype) for r in dec_rngs]
    return {
        "embed": L.dense_init(r_embed, (cfg.vocab_size, cfg.d_model), 1, dtype),
        "enc_layers": jax.tree.map(lambda *xs: jnp.stack(xs), *enc),
        "dec_layers": jax.tree.map(lambda *xs: jnp.stack(xs), *dec),
        "enc_norm": jnp.ones((cfg.d_model,), dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "lm_head": L.dense_init(r_head, (cfg.d_model, cfg.vocab_size), 0, dtype),
    }


def _add_pos(cfg, x, offset=0):
    S = x.shape[1]
    positions = offset + jnp.arange(S)     # (S,) or (B, S) if offset is (B,1)
    pe = L.sinusoid_pos_embed(positions, cfg.d_model)
    if pe.ndim == 2:
        pe = pe[None]
    return x + pe.astype(x.dtype)


def encode(cfg, params, src_embeds):
    """src_embeds: (B, Ss, D) -> encoder output (B, Ss, D)."""
    x = _add_pos(cfg, src_embeds.astype(jnp.dtype(cfg.dtype)))

    def body(h, lp):
        a, _ = L.attention_layer(lp["attn"], cfg, L.rms_norm(h, lp["ln1"], cfg.norm_eps),
                                 use_rope=False, causal=False)
        h = h + a
        h = h + L.mlp_layer(lp["mlp"], L.rms_norm(h, lp["ln2"], cfg.norm_eps))
        return h, None

    x, _ = jax.lax.scan(_remat(body, cfg), x, params["enc_layers"])
    return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)


def _cross_kv(lp, cfg, enc_out):
    """Project encoder output to this layer's cross K/V."""
    B, Ss, _ = enc_out.shape
    K, hd = cfg.num_kv_heads, cfg.head_dim
    k = (enc_out @ lp["xattn"]["wk"]).reshape(B, Ss, K, hd)
    v = (enc_out @ lp["xattn"]["wv"]).reshape(B, Ss, K, hd)
    return k, v


def _dec_layer_seq(cfg, lp, x, enc_out, collect_cache):
    cache_out = {}
    a, (k, v) = L.attention_layer(lp["attn"], cfg,
                                  L.rms_norm(x, lp["ln1"], cfg.norm_eps),
                                  use_rope=False)
    x = x + a
    ck, cv = _cross_kv(lp, cfg, enc_out)
    xa = L.cross_attention_layer(lp["xattn"], cfg,
                                 L.rms_norm(x, lp["ln_x"], cfg.norm_eps), ck, cv)
    x = x + xa
    x = x + L.mlp_layer(lp["mlp"], L.rms_norm(x, lp["ln2"], cfg.norm_eps))
    if collect_cache:
        cache_out = {"k": k, "v": v, "cross_k": ck, "cross_v": cv}
    return x, cache_out


def decode_seq(cfg, params, tgt_tokens, enc_out, collect_cache=False):
    """Teacher-forced decoder pass. Returns (hidden, stacked caches)."""
    x = _add_pos(cfg, params["embed"][tgt_tokens])

    def body(h, lp):
        h, cache_out = _dec_layer_seq(cfg, lp, h, enc_out, collect_cache)
        return h, cache_out

    x, caches = jax.lax.scan(_remat(body, cfg), x, params["dec_layers"])
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps), caches


def forward_train(cfg, params, batch):
    enc_out = encode(cfg, params, batch["src_embeds"])
    hidden, _ = decode_seq(cfg, params, batch["tgt_tokens"], enc_out)
    loss = chunked_ce_loss(cfg, params, hidden, batch["targets"])
    return loss, {"ce_loss": loss, "aux_loss": jnp.zeros((), jnp.float32)}


def prefill(cfg, params, batch, cache_len=None, counters=False):
    """Encode source + teacher-forced decoder prefill; build decode cache.
    `counters`: as `lm.prefill`'s; this family counts nothing."""
    enc_out = encode(cfg, params, batch["src_embeds"])
    tgt = batch["tgt_tokens"]
    B, St = tgt.shape
    hidden, caches = decode_seq(cfg, params, tgt, enc_out, collect_cache=True)
    logits = (hidden[:, -1:] @ _lm_head(cfg, params)).astype(jnp.float32)
    W = cache_len or St
    k, v = caches["k"], caches["v"]
    if W > St:
        padw = ((0, 0), (0, 0), (0, W - St), (0, 0), (0, 0))
        k, v = jnp.pad(k, padw), jnp.pad(v, padw)
    cache = {
        "pos": jnp.full((B,), St, jnp.int32),
        "k": k, "v": v,
        "slot_pos": kvc.prefill_slot_pos(St, W, B),
        "cross_k": caches["cross_k"], "cross_v": caches["cross_v"],
    }
    return (logits, cache, {}) if counters else (logits, cache)


def decode_step(cfg, params, cache, token):
    """One decoder token against self + cross caches."""
    pos = cache["pos"]
    B = token.shape[0]
    x = _add_pos(cfg, params["embed"][token], offset=pos[:, None])

    slot_pos = cache["slot_pos"]
    W = slot_pos.shape[1]
    b_idx = jnp.arange(B)
    slot = (pos % W).astype(jnp.int32)
    slot_pos = slot_pos.at[b_idx, slot].set(pos.astype(jnp.int32))

    def body(h, xs):
        lp, lc = xs
        a, (k_c, v_c) = L.attention_decode_layer(
            lp["attn"], cfg, L.rms_norm(h, lp["ln1"], cfg.norm_eps),
            lc["k"], lc["v"], slot_pos, pos, use_rope=False)
        h = h + a
        xa = L.cross_attention_layer(
            lp["xattn"], cfg, L.rms_norm(h, lp["ln_x"], cfg.norm_eps),
            lc["cross_k"], lc["cross_v"])
        h = h + xa
        h = h + L.mlp_layer(lp["mlp"], L.rms_norm(h, lp["ln2"], cfg.norm_eps))
        return h, {"k": k_c, "v": v_c}

    layer_caches = {"k": cache["k"], "v": cache["v"],
                    "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}
    x, new_kv = jax.lax.scan(body, x, (params["dec_layers"], layer_caches))
    logits = (L.rms_norm(x, params["final_norm"], cfg.norm_eps)
              @ _lm_head(cfg, params)).astype(jnp.float32)
    new_cache = dict(cache)
    new_cache.update(new_kv)
    new_cache["slot_pos"] = slot_pos
    new_cache["pos"] = pos + 1
    return logits, new_cache
