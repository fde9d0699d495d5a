"""Unified model backbone: embeds -> scanned blocks -> norm -> lm head.

One code path covers all ten assigned architectures:

  mixer  = attention (GQA/qk-norm/bias/SWA) | MLA | SSD | hybrid(attn+SSD)
  ffn    = SwiGLU | MoE (+shared experts, leading dense layers)
  stack  = decoder-only | encoder-decoder (cross-attention)
  embed  = tokens | VLM patch-merge | frontend-stub embeddings

Layers are stacked and scanned (``lax.scan``) so the lowered HLO is O(1)
in depth — required for tractable 512-device dry-run compiles — with
optional per-layer remat. Losses use vocab-sharded cross-entropy (logits
are never replicated).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    attention_block, init_attention, init_dense, init_mla, init_mlp,
    mla_block, mlp_block, rms_norm,
)
from repro.models.sharding import batch_axes, shard, shard_residual


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_mixer(key, cfg: ModelConfig, dtype) -> dict:
    if cfg.family == "ssm":
        return {"ssm": ssm_mod.init_ssm(key, cfg, dtype)}
    if cfg.mla.enabled:
        return {"mla": init_mla(key, cfg, dtype)}
    k1, k2 = jax.random.split(key)
    p = {"attn": init_attention(k1, cfg, dtype)}
    if cfg.hybrid_parallel_heads:
        p["ssm"] = ssm_mod.init_ssm(k2, cfg, dtype)
        p["attn_out_norm_scale"] = jnp.ones((cfg.d_model,), dtype)
        p["ssm_out_norm_scale"] = jnp.ones((cfg.d_model,), dtype)
    return p


def _init_ffn(key, cfg: ModelConfig, dtype, dense: bool) -> dict:
    if cfg.moe.enabled and not dense:
        return {"moe": moe_mod.init_moe(key, cfg, dtype)}
    d_ff = (cfg.moe.dense_d_ff or cfg.d_ff) if cfg.moe.enabled else cfg.d_ff
    return {"mlp": init_mlp(key, cfg.d_model, d_ff, dtype)}


def _init_block(key, cfg: ModelConfig, dtype, dense_ffn: bool = False,
                cross_attn: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "pre_norm_scale": jnp.ones((cfg.d_model,), dtype),
        "mixer": _init_mixer(ks[0], cfg, dtype),
        "post_norm_scale": jnp.ones((cfg.d_model,), dtype),
        "ffn": _init_ffn(ks[1], cfg, dtype, dense_ffn),
    }
    if cross_attn:
        p["cross_norm_scale"] = jnp.ones((cfg.d_model,), dtype)
        p["cross"] = init_attention(ks[2], cfg, dtype)
    return p


def _stack_layers(key, cfg: ModelConfig, n: int, dtype,
                  cross_attn: bool = False) -> dict:
    """Init n identical blocks and stack leaves -> leading layer dim."""
    keys = jax.random.split(key, n)
    blocks = [_init_block(k, cfg, dtype, cross_attn=cross_attn)
              for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 8)
    pv = cfg.padded_vocab()
    p = {
        "embed": init_dense(ks[0], pv, cfg.d_model, dtype),
        "final_norm_scale": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = init_dense(ks[1], cfg.d_model, pv, dtype)

    n_scanned = cfg.num_layers - (cfg.moe.first_dense_layers
                                  if cfg.moe.enabled else 0)
    if cfg.moe.enabled and cfg.moe.first_dense_layers:
        p["dense_blocks"] = {
            str(i): _init_block(k, cfg, dtype, dense_ffn=True)
            for i, k in enumerate(
                jax.random.split(ks[2], cfg.moe.first_dense_layers))}
    if cfg.enc_dec:
        p["enc_layers"] = _stack_layers(ks[3], cfg, cfg.encoder_layers,
                                        dtype)
        p["dec_layers"] = _stack_layers(ks[4], cfg, cfg.num_layers, dtype,
                                        cross_attn=True)
    else:
        p["layers"] = _stack_layers(ks[5], cfg, n_scanned, dtype)
    return p


def layer_windows(cfg: ModelConfig, n: int) -> jax.Array:
    """Per-layer attention window (0 = global), scanned alongside params."""
    if cfg.attention_kind != "swa":
        return jnp.zeros((n,), jnp.int32)
    idx = jnp.arange(n)
    is_global = (idx == 0) | (idx == n - 1)
    if cfg.global_attn_every:
        is_global |= (idx % cfg.global_attn_every) == 0
    return jnp.where(is_global, 0, cfg.sliding_window).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _mixer_apply(mp: dict, cfg: ModelConfig, x, positions, window,
                 cache, mrope_positions):
    """Returns (out, new_cache)."""
    if cfg.family == "ssm":
        return ssm_mod.ssm_block(mp["ssm"], cfg, x, cache=cache)
    if cfg.mla.enabled:
        return mla_block(mp["mla"], cfg, x, positions, cache=cache)
    if cfg.hybrid_parallel_heads:
        a_cache = cache["attn"] if cache is not None else None
        s_cache = cache["ssm"] if cache is not None else None
        a_out, a_new = attention_block(
            mp["attn"], cfg, x, positions, window=window, cache=a_cache)
        s_out, s_new = ssm_mod.ssm_block(mp["ssm"], cfg, x, cache=s_cache)
        out = 0.5 * (rms_norm(a_out, mp["attn_out_norm_scale"], cfg.rms_eps)
                     + rms_norm(s_out, mp["ssm_out_norm_scale"],
                                cfg.rms_eps))
        new = (None if a_new is None and s_new is None
               else {"attn": a_new, "ssm": s_new})
        return out, new
    return attention_block(mp["attn"], cfg, x, positions, window=window,
                           cache=cache, mrope_positions=mrope_positions)


def _block_apply(bp: dict, cfg: ModelConfig, x, positions, window,
                 cache, mrope_positions, enc_out=None, causal=True,
                 sequence_parallel=False):
    """One transformer block. Returns (x, new_cache, aux_loss)."""
    h = rms_norm(x, bp["pre_norm_scale"], cfg.rms_eps)
    if cfg.family == "ssm" or cfg.mla.enabled or cfg.hybrid_parallel_heads:
        mix, new_cache = _mixer_apply(bp["mixer"], cfg, h, positions,
                                      window, cache, mrope_positions)
    else:
        self_cache = (cache.get("self") if isinstance(cache, dict)
                      and "self" in cache else cache)
        mix, new_self = attention_block(
            bp["mixer"]["attn"], cfg, h, positions, causal=causal,
            window=window, cache=self_cache,
            mrope_positions=mrope_positions)
        new_cache = new_self
    x = x + mix
    x = shard_residual(x, sequence_parallel)

    if enc_out is not None:
        # cross-attention (decoder): KV from encoder output, no rope mixing
        hc = rms_norm(x, bp["cross_norm_scale"], cfg.rms_eps)
        c_out, _ = _cross_attention(bp["cross"], cfg, hc, enc_out)
        x = x + c_out
        if isinstance(cache, dict) and "self" in cache:
            new_cache = {"self": new_cache}

    h2 = rms_norm(x, bp["post_norm_scale"], cfg.rms_eps)
    if "moe" in bp["ffn"]:
        f, aux = moe_mod.moe_ffn(bp["ffn"]["moe"], cfg, h2)
    else:
        f, aux = mlp_block(bp["ffn"]["mlp"], h2), jnp.float32(0)
    x = x + f
    x = shard_residual(x, sequence_parallel)
    return x, new_cache, aux


def _cross_attention(params: dict, cfg: ModelConfig, x, enc_out):
    """Cross-attention: q from decoder x, k/v from encoder output."""
    from repro.models.layers import attention_core
    b, s, _ = x.shape
    se = enc_out.shape[1]
    hd = cfg.resolved_head_dim()
    q = (x @ params["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = (enc_out @ params["wk"]).reshape(b, se, cfg.num_kv_heads, hd)
    v = (enc_out @ params["wv"]).reshape(b, se, cfg.num_kv_heads, hd)
    out = attention_core(q, k, v, causal=False)
    return out.reshape(b, s, cfg.num_heads * hd) @ params["wo"], None


# ---------------------------------------------------------------------------
# Scanned stack
# ---------------------------------------------------------------------------

_REMAT_POLICY = "full"   # full | dots | none  (perf knob, §Perf)


def set_remat_policy(name: str) -> None:
    global _REMAT_POLICY
    assert name in ("full", "dots", "none"), name
    _REMAT_POLICY = name


def _scan_stack(layers: dict, cfg: ModelConfig, x, positions, windows,
                caches, mrope_positions, enc_out=None, causal=True,
                remat=False, sequence_parallel=False):
    """Scan blocks over the stacked-layer pytree.

    caches: stacked cache pytree (leading L dim) or None.
    Returns (x, new_caches, aux_sum).
    """
    has_cache = caches is not None

    def body(carry, xs):
        x = carry
        if has_cache:
            bp, w, cache = xs
        else:
            (bp, w), cache = xs, None
        x, new_cache, aux = _block_apply(
            bp, cfg, x, positions, w, cache, mrope_positions,
            enc_out=enc_out, causal=causal,
            sequence_parallel=sequence_parallel)
        out = (new_cache, aux) if has_cache else aux
        return x, out

    if remat and _REMAT_POLICY != "none":
        policy = (jax.checkpoint_policies.nothing_saveable
                  if _REMAT_POLICY == "full"
                  else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        body = jax.checkpoint(body, policy=policy)
    xs = (layers, windows, caches) if has_cache else (layers, windows)
    x, outs = jax.lax.scan(body, x, xs)
    if has_cache:
        new_caches, auxs = outs
        return x, new_caches, jnp.sum(auxs)
    return x, None, jnp.sum(outs)


# ---------------------------------------------------------------------------
# Forward / loss / decode
# ---------------------------------------------------------------------------

def embed_inputs(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    """Token embedding with VLM patch-merge / frontend-stub support."""
    if cfg.embedding_frontend_stub and "enc_embeds" not in batch \
            and "embeds" in batch:
        return batch["embeds"]
    x = params["embed"][batch["tokens"]]               # (B, S, D)
    if cfg.mrope and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(x.dtype)     # (B, P, D)
        p = pe.shape[1]
        x = jnp.concatenate([pe, x[:, p:]], axis=1)
    return x


def forward(params: dict, cfg: ModelConfig, batch: dict, *,
            caches=None, remat: bool = False,
            sequence_parallel: bool = False):
    """Full forward. batch keys: tokens (B,S)[, positions, mrope_positions,
    patch_embeds, enc_embeds]. Returns (logits, new_caches, aux)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    mrope_positions = batch.get("mrope_positions")

    enc_out = None
    if cfg.enc_dec:
        enc_x = batch["enc_embeds"]                    # frontend stub
        enc_pos = jnp.broadcast_to(
            jnp.arange(enc_x.shape[1], dtype=jnp.int32),
            (enc_x.shape[0], enc_x.shape[1]))
        wins_e = layer_windows(cfg, cfg.encoder_layers)
        enc_out, _, _ = _scan_stack(
            params["enc_layers"], cfg, enc_x, enc_pos, wins_e, None, None,
            causal=False, remat=remat,
            sequence_parallel=sequence_parallel)

    x = embed_inputs(params, cfg, batch)
    x = shard_residual(x, sequence_parallel)
    aux_total = jnp.float32(0)

    if cfg.moe.enabled and cfg.moe.first_dense_layers and \
            "dense_blocks" in params:
        for i in sorted(params["dense_blocks"], key=int):
            bp = params["dense_blocks"][i]
            cache_i = caches["dense"][i] if caches is not None else None
            x, nc, aux = _block_apply(
                bp, cfg, x, positions, jnp.int32(0), cache_i,
                mrope_positions, sequence_parallel=sequence_parallel)
            if caches is not None:
                caches["dense"][i] = nc
            aux_total += aux

    layer_key = "dec_layers" if cfg.enc_dec else "layers"
    n_scanned = (cfg.num_layers if not cfg.moe.enabled
                 else cfg.num_layers - cfg.moe.first_dense_layers)
    wins = layer_windows(cfg, n_scanned)
    stack_caches = caches["scan"] if caches is not None else None
    x, new_scan_caches, aux = _scan_stack(
        params[layer_key], cfg, x, positions, wins, stack_caches,
        mrope_positions, enc_out=enc_out, remat=remat,
        sequence_parallel=sequence_parallel)
    aux_total += aux

    x = rms_norm(x, params["final_norm_scale"], cfg.rms_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = x @ head                                  # (B, S, V_padded)
    logits = shard(logits, P(batch_axes(), None, "model"))

    new_caches = None
    if caches is not None:
        new_caches = dict(caches)
        new_caches["scan"] = new_scan_caches
    return logits, new_caches, aux_total


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  vocab: int) -> jax.Array:
    """Vocab-sharded stable CE: never gathers the full vocab axis."""
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, vocab, dtype=jnp.float32)
    picked = jnp.sum(shifted * onehot, axis=-1) + m[..., 0]
    return jnp.mean(lse - picked)


def loss_fn(params: dict, cfg: ModelConfig, batch: dict, *,
            remat: bool = False, sequence_parallel: bool = False,
            aux_weight: Optional[float] = None):
    logits, _, aux = forward(params, cfg, batch, remat=remat,
                             sequence_parallel=sequence_parallel)
    loss = cross_entropy(logits, batch["labels"], cfg.padded_vocab())
    if cfg.moe.enabled:
        w = cfg.moe.aux_loss_weight if aux_weight is None else aux_weight
        loss = loss + w * aux
    return loss


# ---------------------------------------------------------------------------
# KV caches (serving)
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_seq: int,
                dtype=jnp.bfloat16) -> dict:
    """Stacked (leading layer dim) cache pytree for the scanned stack."""
    hd = cfg.resolved_head_dim()

    def one_layer():
        if cfg.family == "ssm":
            return ssm_mod.init_ssm_cache(cfg, batch, dtype)
        if cfg.mla.enabled:
            m = cfg.mla
            return {
                "c_kv": jnp.zeros((batch, max_seq, m.kv_lora_rank), dtype),
                "k_rope": jnp.zeros((batch, max_seq, m.qk_rope_head_dim),
                                    dtype),
                "pos": jnp.int32(0),
            }
        attn = {
            "k": jnp.zeros((batch, max_seq, cfg.num_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, max_seq, cfg.num_kv_heads, hd), dtype),
            "pos": jnp.int32(0),
        }
        if cfg.hybrid_parallel_heads:
            return {"attn": attn,
                    "ssm": ssm_mod.init_ssm_cache(cfg, batch, dtype)}
        if cfg.enc_dec:
            return {"self": attn}
        return attn

    n_scanned = (cfg.num_layers if not cfg.moe.enabled
                 else cfg.num_layers - cfg.moe.first_dense_layers)
    layers = [one_layer() for _ in range(n_scanned)]
    caches = {"scan": jax.tree.map(lambda *xs: jnp.stack(xs), *layers)}
    if cfg.moe.enabled and cfg.moe.first_dense_layers:
        caches["dense"] = {str(i): one_layer()
                           for i in range(cfg.moe.first_dense_layers)}
    return caches
