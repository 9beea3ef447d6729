"""Composable decoder stacks for all assigned architecture families.

Layer stacks are scanned (``lax.scan`` over stacked per-layer params) so
the lowered HLO stays compact at 26-80 layers, with optional remat.
Families:
  dense / vlm / audio : [norm -> GQA attn -> norm -> GLU MLP] x L
  moe                 : MLP replaced by top-k MoE
  ssm                 : [norm -> mamba2 block] x L
  hybrid              : ssm stack + one *shared* attn+MLP block applied
                        every `hybrid_attn_every` layers (zamba2)
Decode paths mirror each stack with KV / SSM caches.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    embed,
    glu_mlp,
    init_embedding,
    init_glu_mlp,
    init_rms_norm,
    rms_norm,
    unembed,
)

# ---------------------------------------------------------------------------
# Param init
# ---------------------------------------------------------------------------


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def init_dense_layer(key: jax.Array, cfg: ArchConfig) -> dict:
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": init_rms_norm(cfg.d_model),
        "attn": attn.init_attention(
            k1, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.attn_bias, dt,
        ),
        "ln2": init_rms_norm(cfg.d_model),
    }
    if cfg.family == "moe":
        p["moe"] = moe_lib.init_moe(
            k2, cfg.d_model, cfg.d_ff, cfg.num_experts, dt
        )
    else:
        p["mlp"] = init_glu_mlp(k2, cfg.d_model, cfg.d_ff, dt)
    if cfg.post_block_norms:
        p["ln1_post"] = init_rms_norm(cfg.d_model)
        p["ln2_post"] = init_rms_norm(cfg.d_model)
    return p


def init_params(key: jax.Array, cfg: ArchConfig) -> dict:
    dt = _dtype(cfg)
    k_emb, k_layers, k_head, k_shared = jax.random.split(key, 4)
    params: dict[str, Any] = {
        "embed": init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dt),
        "final_norm": init_rms_norm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = init_embedding(
            k_head, cfg.vocab_size, cfg.d_model, dt
        )
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        params["layers"] = jax.vmap(
            lambda k: init_dense_layer(k, cfg)
        )(layer_keys)
    elif cfg.family == "ssm":
        params["layers"] = jax.vmap(
            lambda k: {
                "ln": init_rms_norm(cfg.d_model),
                "mamba": ssm_lib.init_mamba_block(k, cfg, dt),
            }
        )(layer_keys)
    elif cfg.family == "hybrid":
        params["layers"] = jax.vmap(
            lambda k: {
                "ln": init_rms_norm(cfg.d_model),
                "mamba": ssm_lib.init_mamba_block(k, cfg, dt),
            }
        )(layer_keys)
        params["shared"] = init_dense_layer(k_shared, cfg)
    else:
        raise ValueError(cfg.family)
    return params


# ---------------------------------------------------------------------------
# Layer-level forwards
# ---------------------------------------------------------------------------


def constrain_batch_dim(x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Shard dim 0 (batch) over "model" — used around attention so that
    archs whose head counts don't divide the TP axis (starcoder2: 24
    heads on 16 chips) compute attention batch-parallel instead of
    replicated. Active under act_shard == "batch"."""
    if cfg.act_shard != "batch":
        return x
    mesh = jax.sharding.get_abstract_mesh()
    names = mesh.axis_names
    if "model" not in names:
        return x
    n = dict(mesh.shape)["model"]
    if x.shape[0] % n:
        return x
    from jax.sharding import PartitionSpec as P

    return jax.lax.with_sharding_constraint(
        x, P(*(["model"] + [None] * (x.ndim - 1)))
    )


def constrain_acts(h: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Optional activation-sharding constraint over the "model" axis.

    Applied at block boundaries ((B, S, d) residual stream, possibly
    under a node-dim vmap). No-op when cfg.act_shard == "none", when no
    mesh is in context, or when the dim doesn't divide the axis.
    """
    if cfg.act_shard == "none":
        return h
    mesh = jax.sharding.get_abstract_mesh()
    names = mesh.axis_names
    if "model" not in names:
        return h
    n = dict(mesh.shape)["model"]
    dim = 0 if cfg.act_shard == "batch" else 1
    if h.ndim < 3 or h.shape[dim] % n:
        return h
    from jax.sharding import PartitionSpec as P

    spec = [None] * h.ndim
    spec[dim] = "model"
    return jax.lax.with_sharding_constraint(h, P(*spec))


def _is_local_flags(cfg: ArchConfig):
    """Per-layer sliding-window flag (STATIC numpy — also used for cache
    layout decisions under eval_shape).

    gemma2: layers alternate local (even) / global (odd). Pure-SWA archs
    (danube): every layer local. Others: none.
    """
    import numpy as np

    idx = np.arange(cfg.num_layers)
    if cfg.local_global_period > 0:
        return (idx % cfg.local_global_period) != (cfg.local_global_period - 1)
    if cfg.sliding_window is not None:
        return np.ones((cfg.num_layers,), bool)
    return np.zeros((cfg.num_layers,), bool)


def dense_block(
    p: dict,
    h: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    is_local: jax.Array,
    *,
    want_kv: bool,
):
    """One dense/moe block on full sequences. Returns (h, kv, metrics)."""
    hn = rms_norm(h, p["ln1"], cfg.norm_eps)
    q, k, v = attn.qkv_project(
        p["attn"], hn, cfg.num_heads, cfg.num_kv_heads, cfg.rope_theta, positions
    )
    q = constrain_batch_dim(q, cfg)
    k = constrain_batch_dim(k, cfg)
    v = constrain_batch_dim(v, cfg)
    flash = functools.partial(
        attn.flash_attention,
        q, k, v,
        q_positions=positions,
        k_positions=positions,
        causal=True,
        attn_softcap=cfg.attn_logit_softcap,
    )
    if cfg.sliding_window is None:
        out = flash(window=None)
    elif cfg.local_global_period > 0:
        out = lax.cond(
            is_local,
            lambda: flash(window=cfg.sliding_window),
            lambda: flash(window=None),
        )
    else:
        out = flash(window=cfg.sliding_window)
    out = attn.out_project(p["attn"], out)
    if cfg.post_block_norms:
        out = rms_norm(out, p["ln1_post"], cfg.norm_eps)
    h = h + out

    hn = rms_norm(h, p["ln2"], cfg.norm_eps)
    metrics = {}
    if "moe" in p:
        mlp_out, metrics = moe_lib.moe_ffn(
            p["moe"], hn,
            top_k=cfg.experts_per_token,
            capacity_factor=cfg.capacity_factor,
        )
    else:
        mlp_out = glu_mlp(hn, p["mlp"], cfg.mlp_activation)
    if cfg.post_block_norms:
        mlp_out = rms_norm(mlp_out, p["ln2_post"], cfg.norm_eps)
    h = constrain_acts(h + mlp_out, cfg)
    kv = (k, v) if want_kv else None
    return h, kv, metrics


def dense_block_decode(
    p: dict,
    h: jax.Array,  # (B, 1, d)
    pos: jax.Array,  # () absolute position, or (B,) ragged per-row
    cache_k: jax.Array,  # (B, Sc, K, hd)
    cache_v: jax.Array,
    cfg: ArchConfig,
    *,
    windowed: bool,
):
    """One block, one token, against a cache. Returns (h, ck, cv)."""
    hn = rms_norm(h, p["ln1"], cfg.norm_eps)
    rope_pos = pos[:, None] if jnp.ndim(pos) else pos[None]
    q, k, v = attn.qkv_project(
        p["attn"], hn, cfg.num_heads, cfg.num_kv_heads, cfg.rope_theta,
        rope_pos,
    )
    cache_k, cache_v = attn.decode_update_layer(
        cache_k, cache_v, k, v, pos, windowed=windowed
    )
    out = attn.decode_attend(
        q, cache_k, cache_v, pos,
        windowed=windowed,
        window=cfg.sliding_window if windowed else None,
        cap=cfg.attn_logit_softcap,
    )
    out = attn.out_project(p["attn"], out)
    if cfg.post_block_norms:
        out = rms_norm(out, p["ln1_post"], cfg.norm_eps)
    h = h + out
    hn = rms_norm(h, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        mlp_out, _ = moe_lib.moe_ffn(
            p["moe"], hn,
            top_k=cfg.experts_per_token,
            capacity_factor=cfg.capacity_factor,
        )
    else:
        mlp_out = glu_mlp(hn, p["mlp"], cfg.mlp_activation)
    if cfg.post_block_norms:
        mlp_out = rms_norm(mlp_out, p["ln2_post"], cfg.norm_eps)
    return h + mlp_out, cache_k, cache_v


# ---------------------------------------------------------------------------
# Full-sequence stacks (train / prefill)
# ---------------------------------------------------------------------------


def dense_stack(
    params: dict,
    h: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    *,
    want_kv: bool,
):
    """Scan the dense/moe stack. Returns (h, stacked kv | None, metrics)."""
    flags = _is_local_flags(cfg)
    h = constrain_acts(h, cfg)

    def body(carry, xs):
        p, is_local = xs
        new_h, kv, metrics = dense_block(
            p, carry, positions, cfg, is_local, want_kv=want_kv
        )
        ys = (kv, metrics) if want_kv else (None, metrics)
        return new_h, ys

    if cfg.remat:
        body = jax.checkpoint(body)
    h, (kvs, metrics) = lax.scan(
        body, h, (params["layers"], jnp.asarray(flags))
    )
    metrics = {k: jnp.mean(v) for k, v in metrics.items()}
    return h, kvs, metrics


def ssm_stack(params: dict, h: jax.Array, cfg: ArchConfig, *, want_state: bool):
    """Scan the pure-SSM stack. Returns (h, stacked (state, conv) | None)."""

    h = constrain_acts(h, cfg)

    def body(carry, p):
        hn = rms_norm(carry, p["ln"], cfg.norm_eps)
        out, state, conv_tail = ssm_lib.mamba_forward(p["mamba"], hn, cfg)
        ys = (state, conv_tail) if want_state else None
        return constrain_acts(carry + out, cfg), ys

    if cfg.remat:
        body = jax.checkpoint(body)
    h, ys = lax.scan(body, h, params["layers"])
    return h, ys


def hybrid_stack(
    params: dict,
    h: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    *,
    want_cache: bool,
):
    """Zamba2-style stack: shared attn block every k SSM layers.

    Returns (h, (ssm_cache_stacks, shared_kv_stack) | None).
    """
    k = cfg.hybrid_attn_every
    L = cfg.num_layers
    app_points = list(range(0, L, k))  # layers preceded by the shared block

    h = constrain_acts(h, cfg)

    def seg_body(carry, p):
        hn = rms_norm(carry, p["ln"], cfg.norm_eps)
        out, state, conv_tail = ssm_lib.mamba_forward(p["mamba"], hn, cfg)
        ys = (state, conv_tail) if want_cache else None
        return constrain_acts(carry + out, cfg), ys

    if cfg.remat:
        seg_body = jax.checkpoint(seg_body)

    shared_kvs = []
    ssm_states, ssm_convs = [], []
    for si, start in enumerate(app_points):
        end = min(start + k, L)
        # shared attention block (same params every application)
        sh, kv, _ = dense_block(
            params["shared"], h, positions, cfg,
            jnp.asarray(False),
            want_kv=want_cache,
        )
        h = sh
        if want_cache:
            shared_kvs.append(kv)
        seg_params = jax.tree.map(lambda x: x[start:end], params["layers"])
        h, ys = lax.scan(seg_body, h, seg_params)
        if want_cache:
            ssm_states.append(ys[0])
            ssm_convs.append(ys[1])
        del si
    if not want_cache:
        return h, None
    cache = (
        (
            jnp.concatenate(ssm_states, 0),
            jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *ssm_convs),
        ),
        (
            jnp.stack([kv[0] for kv in shared_kvs]),
            jnp.stack([kv[1] for kv in shared_kvs]),
        ),
    )
    return h, cache
