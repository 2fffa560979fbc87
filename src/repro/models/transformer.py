"""Decoder LM / encoder-decoder assembly with scanned layer periods.

The layer stack is grouped into repeating *periods* (cfg.pattern); params
and caches are stacked over periods so the whole stack lowers to a single
``lax.scan`` — which keeps HLO size O(period) instead of O(n_layers) for
the 512-device dry-run compiles, and gives the standard remat point.

Paths:
  lm_loss      — training: tokens → chunked-vocab xent (+ MoE aux)
  lm_hidden    — shared trunk
  decode_step  — single-token serve step over per-layer caches
  encode       — encoder trunk (enc-dec archs); decoder cross-attends
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding import constraint
from .attention import (AttnConfig, attn_apply, attn_decode, attn_init,
                        blockwise_attention, cross_attn_apply,
                        cross_attn_init, init_cache as attn_init_cache)
from .common import dense_init, embed_init, make_norm
from .ffn import ffn_apply, ffn_init
from .moe import moe_apply, moe_init
from .ssm import ssm_apply, ssm_decode, ssm_init, ssm_init_cache
from .xlstm import (mlstm_apply, mlstm_decode, mlstm_init, mlstm_init_cache,
                    slstm_apply, slstm_decode, slstm_init, slstm_init_cache)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Single block (one layer): init / train-apply / decode-apply
# ---------------------------------------------------------------------------

def _block_attn_cfg(cfg, kind: str) -> AttnConfig:
    window = cfg.sliding_window if kind == "L" else None
    return cfg.attn_config(window=window)


def block_init(key, cfg, kind: str, use_moe: bool, *, cross: bool = False,
               dtype=jnp.float32) -> Params:
    ninit, _ = make_norm(cfg.norm)
    ks = jax.random.split(key, 6)
    p: Params = {"norm1": ninit(cfg.d_model, dtype)}
    if kind in "ALG":
        p["attn"] = attn_init(ks[0], _block_attn_cfg(cfg, kind), dtype)
    elif kind == "M":
        p["ssm"] = ssm_init(ks[0], cfg.ssm_config(), dtype)
    elif kind == "m":
        p["mlstm"] = mlstm_init(ks[0], cfg.xlstm_config(), dtype)
        return p                                   # self-contained block
    elif kind == "s":
        p["slstm"] = slstm_init(ks[0], cfg.xlstm_config(), dtype)
        return p
    else:
        raise ValueError(kind)
    if cross:
        p["norm_x"] = ninit(cfg.d_model, dtype)
        p["cross"] = cross_attn_init(ks[2], cfg.attn_config(), cfg.d_model, dtype)
    p["norm2"] = ninit(cfg.d_model, dtype)
    if use_moe:
        p["moe"] = moe_init(ks[1], cfg.d_model, cfg.moe, dtype)
    else:
        p["ffn"] = ffn_init(ks[1], cfg.ffn_config(), dtype)
    return p


def block_apply(p: Params, x: jnp.ndarray, cfg, kind: str, use_moe: bool,
                *, causal: bool = True, memory: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Training/prefill path; returns (x, moe_aux)."""
    _, norm = make_norm(cfg.norm)
    aux = jnp.zeros((), jnp.float32)
    h = norm(p["norm1"], x)
    if kind in "ALG":
        acfg = _block_attn_cfg(cfg, kind)
        if causal:
            h = attn_apply(p["attn"], h, acfg)
        else:  # encoder: bidirectional full attention
            b, t, _ = h.shape
            q = (h @ p["attn"]["wq"]).reshape(b, t, acfg.n_heads, acfg.head_dim)
            k = (h @ p["attn"]["wk"]).reshape(b, t, acfg.n_kv_heads, acfg.head_dim)
            v = (h @ p["attn"]["wv"]).reshape(b, t, acfg.n_kv_heads, acfg.head_dim)
            from .common import apply_rope
            pos = jnp.arange(t)
            q = apply_rope(q, pos, acfg.rope_theta)
            k = apply_rope(k, pos, acfg.rope_theta)
            o = blockwise_attention(q, k, v, causal=False,
                                    q_chunk=acfg.q_chunk, kv_chunk=acfg.kv_chunk)
            h = o.reshape(b, t, -1) @ p["attn"]["wo"]
    elif kind == "M":
        h = ssm_apply(p["ssm"], h, cfg.ssm_config())
    elif kind == "m":
        return x + mlstm_apply(p["mlstm"], h, cfg.xlstm_config()), aux
    elif kind == "s":
        return x + slstm_apply(p["slstm"], h, cfg.xlstm_config()), aux
    x = x + h
    x = constraint(x, "act_btd")
    if memory is not None and "cross" in p:
        h = norm(p["norm_x"], x)
        x = x + cross_attn_apply(p["cross"], h, memory, cfg.attn_config())
    h = norm(p["norm2"], x)
    if use_moe:
        h, aux = moe_apply(p["moe"], h, cfg.moe)
    else:
        h = ffn_apply(p["ffn"], h, cfg.ffn_config())
    x = x + h
    return constraint(x, "act_btd"), aux


def block_init_cache(cfg, kind: str, batch: int, max_len: int, dtype) -> Params:
    if kind in "ALG":
        return attn_init_cache(_block_attn_cfg(cfg, kind), batch, max_len, dtype)
    if kind == "M":
        return ssm_init_cache(cfg.ssm_config(), batch, dtype)
    if kind == "m":
        return mlstm_init_cache(cfg.xlstm_config(), batch)
    if kind == "s":
        return slstm_init_cache(cfg.xlstm_config(), batch)
    raise ValueError(kind)


def block_decode(p: Params, x: jnp.ndarray, cache: Params, index, cfg,
                 kind: str, use_moe: bool,
                 memory: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, Params]:
    _, norm = make_norm(cfg.norm)
    h = norm(p["norm1"], x)
    if kind in "ALG":
        h, cache = attn_decode(p["attn"], h, cache, index, _block_attn_cfg(cfg, kind))
    elif kind == "M":
        h, cache = ssm_decode(p["ssm"], h, cache, cfg.ssm_config())
    elif kind == "m":
        h, cache = mlstm_decode(p["mlstm"], h, cache, cfg.xlstm_config())
        return x + h, cache
    elif kind == "s":
        h, cache = slstm_decode(p["slstm"], h, cache, cfg.xlstm_config())
        return x + h, cache
    x = x + h
    if memory is not None and "cross" in p:
        h = norm(p["norm_x"], x)
        x = x + cross_attn_apply(p["cross"], h, memory, cfg.attn_config())
    h = norm(p["norm2"], x)
    if use_moe:
        h, _ = moe_apply(p["moe"], h, cfg.moe)
    else:
        h = ffn_apply(p["ffn"], h, cfg.ffn_config())
    return x + h, cache


# ---------------------------------------------------------------------------
# Stack assembly
# ---------------------------------------------------------------------------

def _embed_lookup(embed: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Embedding lookup that stays efficient under vocab sharding.

    XLA's SPMD partitioner lowers a gather from a vocab-sharded table into
    per-element u32 select masks — measured at 16–20 GiB PER TENSOR on the
    8192-d/64k-vocab cells.  When the launcher installs a mesh in the
    sharding rules ("__mesh__" + "embed_vocab_axis"), we instead shard_map
    the textbook pattern: local gather of the owned vocab slice, mask,
    psum over the vocab axis.  Exact same math; collective is one psum of
    the (tokens × d) output.
    """
    from repro.sharding.context import current_rules
    rules = current_rules() or {}
    mesh = rules.get("__mesh__")
    vaxis = rules.get("embed_vocab_axis")
    if mesh is None or vaxis is None:
        return embed[tokens]
    from jax.sharding import PartitionSpec as P
    v, d = embed.shape
    n = mesh.shape[vaxis]
    if n <= 1 or v % n != 0:
        return embed[tokens]
    vs = v // n
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    tok_spec = P(dp, *([None] * (tokens.ndim - 1))) \
        if tokens.shape[0] % dp_n == 0 else P(*([None] * tokens.ndim))

    def f(emb, toks):
        lo = jax.lax.axis_index(vaxis) * vs
        idx = toks - lo
        ok = (idx >= 0) & (idx < vs)
        safe = jnp.clip(idx, 0, vs - 1)
        out = emb[safe] * ok[..., None].astype(emb.dtype)
        return jax.lax.psum(out, vaxis)

    out_spec = P(*tok_spec, None)
    return jax.shard_map(f, mesh=mesh,
                         in_specs=(P(vaxis, None), tok_spec),
                         out_specs=out_spec, check_vma=False)(embed, tokens)


def _period_layout(cfg) -> Tuple[Tuple[str, bool], ...]:
    """(kind, use_moe) per position within one period of the decoder."""
    kinds = cfg.layer_kinds()
    period = len(cfg.pattern)
    start = cfg.n_dense_layers
    out = []
    for pos in range(period):
        idx = start + pos
        out.append((kinds[idx], cfg.layer_uses_moe(idx)))
    return tuple(out)


def lm_init(key, cfg, dtype=None) -> Params:
    from .common import dtype_of
    dtype = dtype or dtype_of(cfg.dtype)
    keys = jax.random.split(key, 8)
    ninit, _ = make_norm(cfg.norm)
    p: Params = {"embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype)}
    if cfg.frontend:
        p["frontend_proj"] = dense_init(keys[1], cfg.frontend_dim or cfg.d_model,
                                        cfg.d_model, dtype)
    # unrolled dense prefix (e.g. deepseek layer 0)
    if cfg.n_dense_layers:
        kinds = cfg.layer_kinds()
        pk = jax.random.split(keys[2], cfg.n_dense_layers)
        p["prefix"] = [block_init(pk[i], cfg, kinds[i], False, dtype=dtype)
                       for i in range(cfg.n_dense_layers)]
    # scanned periods
    layout = _period_layout(cfg)
    n_periods = (cfg.n_layers - cfg.n_dense_layers) // len(cfg.pattern)
    cross = cfg.enc_dec
    pkeys = jax.random.split(keys[3], n_periods)
    p["layers"] = {}
    for pos, (kind, use_moe) in enumerate(layout):
        init_one = lambda k, kind=kind, um=use_moe: block_init(
            k, cfg, kind, um, cross=cross, dtype=dtype)
        p["layers"][f"b{pos}"] = jax.vmap(init_one)(pkeys)
    p["final_norm"] = ninit(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(keys[4], cfg.d_model, cfg.vocab_size, dtype)
    # encoder stack (enc-dec)
    if cfg.enc_dec:
        ekeys = jax.random.split(keys[5], cfg.n_enc_layers + 1)
        p["encoder"] = {
            "layers": [block_init(ekeys[i], cfg, cfg.enc_pattern[i % len(cfg.enc_pattern)],
                                  False, dtype=dtype)
                       for i in range(cfg.n_enc_layers)],
            "final_norm": ninit(cfg.d_model, dtype),
        }
    return p


def encode(params: Params, frontend_embeds: jnp.ndarray, cfg) -> jnp.ndarray:
    """Encoder trunk over stub frontend embeddings (B, F, frontend_dim)."""
    _, norm = make_norm(cfg.norm)
    x = frontend_embeds
    if "frontend_proj" in params:
        x = x @ params["frontend_proj"]
    for i, lp in enumerate(params["encoder"]["layers"]):
        kind = cfg.enc_pattern[i % len(cfg.enc_pattern)]
        layer = lambda lp, x: block_apply(lp, x, cfg, kind, False,
                                          causal=False)
        if cfg.remat:
            # unrolled stack: without per-layer remat the encoder keeps
            # every intermediate live through the decoder's backward
            # (measured +30 GiB on seamless train_4k)
            layer = jax.checkpoint(layer, prevent_cse=False)
        x, _ = layer(lp, x)
    return norm(params["encoder"]["final_norm"], x)


def lm_hidden(params: Params, tokens: jnp.ndarray, cfg,
              frontend_embeds: Optional[jnp.ndarray] = None,
              memory: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens (B, T) → hidden (B, T', d), plus accumulated MoE aux loss."""
    x = _embed_lookup(params["embed"], tokens)
    if frontend_embeds is not None and not cfg.enc_dec:
        fe = frontend_embeds
        if "frontend_proj" in params:
            fe = fe @ params["frontend_proj"]
        x = jnp.concatenate([fe.astype(x.dtype), x], axis=1)
    x = constraint(x, "act_btd")
    aux = jnp.zeros((), jnp.float32)
    for i in range(cfg.n_dense_layers):
        kinds = cfg.layer_kinds()
        x, a = block_apply(params["prefix"][i], x, cfg, kinds[i], False,
                           memory=memory)
        aux += a

    layout = _period_layout(cfg)

    def period_body(x, layer_params):
        aux_p = jnp.zeros((), jnp.float32)
        for pos, (kind, use_moe) in enumerate(layout):
            x, a = block_apply(layer_params[f"b{pos}"], x, cfg, kind, use_moe,
                               memory=memory)
            aux_p += a
        return x, aux_p

    if cfg.remat:
        period_body = jax.checkpoint(period_body, prevent_cse=False)

    def scan_body(carry, layer_params):
        x, aux = carry
        # "act_stash" (installed by the launcher for ≥100B cells) shards
        # the period-boundary residual over the model axis BEFORE it is
        # saved as the remat stash — the stash is the dominant live buffer
        # at 64–72 layers, and this pins it at 1/model_n size for one
        # all-gather per period per direction.
        x = constraint(x, "act_stash")
        x, a = period_body(x, layer_params)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(scan_body, (x, aux), params["layers"],
                               unroll=cfg.scan_unroll)
    _, norm = make_norm(cfg.norm)
    return norm(params["final_norm"], x), aux


def lm_head_weight(params: Params, cfg) -> jnp.ndarray:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return constraint(w, "head_dv")


def chunked_xent(h2d: jnp.ndarray, targets: jnp.ndarray, w_head: jnp.ndarray,
                 *, chunk: int = 4096, unroll: int = 1) -> jnp.ndarray:
    """Mean next-token xent without materializing (T, V) logits.

    h2d: (T, d); targets: (T,) with -1 = pad; w_head: (d, V)."""
    t = h2d.shape[0]
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:
        h2d = jnp.pad(h2d, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad), constant_values=-1)
    nchunk = h2d.shape[0] // chunk
    hc = h2d.reshape(nchunk, chunk, -1)
    tc = targets.reshape(nchunk, chunk)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one(hx, tx):
        logits = hx.astype(jnp.float32) @ w_head.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(tx, 0)[:, None], axis=-1)[:, 0]
        valid = (tx >= 0).astype(jnp.float32)
        return ((lse - tgt) * valid).sum(), valid.sum()

    def body(carry, xs):
        l, n = one(*xs)
        return (carry[0] + l, carry[1] + n), None

    (loss_sum, n_valid), _ = jax.lax.scan(body, (0.0, 0.0), (hc, tc),
                                          unroll=unroll)
    return loss_sum / jnp.maximum(n_valid, 1.0)


def lm_loss(params: Params, batch: Dict[str, jnp.ndarray], cfg) -> jnp.ndarray:
    """batch: tokens (B, T) [+ frontend_embeds]; next-token LM loss."""
    memory = None
    fe = batch.get("frontend_embeds")
    if cfg.enc_dec:
        memory = encode(params, fe, cfg)
        fe = None
    h, aux = lm_hidden(params, batch["tokens"][:, :-1], cfg,
                       frontend_embeds=fe, memory=memory)
    targets = batch["tokens"][:, 1:]
    if fe is not None:
        # frontend positions are prepended; no LM targets for them
        b, f = fe.shape[0], fe.shape[1]
        pad = jnp.full((b, f), -1, targets.dtype)
        targets = jnp.concatenate([pad, targets], axis=1)
    d = h.shape[-1]
    loss = chunked_xent(h.reshape(-1, d), targets.reshape(-1),
                        lm_head_weight(params, cfg), unroll=cfg.scan_unroll)
    return loss + aux


# ---------------------------------------------------------------------------
# Decode (serving)
# ---------------------------------------------------------------------------

def init_caches(cfg, batch: int, max_len: int, dtype) -> Params:
    kinds = cfg.layer_kinds()
    caches: Params = {}
    if cfg.n_dense_layers:
        caches["prefix"] = [
            block_init_cache(cfg, kinds[i], batch, max_len, dtype)
            for i in range(cfg.n_dense_layers)]
    layout = _period_layout(cfg)
    n_periods = (cfg.n_layers - cfg.n_dense_layers) // len(cfg.pattern)
    caches["layers"] = {}
    for pos, (kind, _) in enumerate(layout):
        one = block_init_cache(cfg, kind, batch, max_len, dtype)
        caches["layers"][f"b{pos}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_periods,) + x.shape), one)
    return caches


def decode_step(params: Params, token: jnp.ndarray, caches: Params,
                index: jnp.ndarray, cfg,
                memory: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Params]:
    """token (B,) int32 → logits (B, V); updates caches functionally."""
    x = _embed_lookup(params["embed"], token)[:, None, :]   # (B, 1, d)
    kinds = cfg.layer_kinds()
    new_caches: Params = {}
    if cfg.n_dense_layers:
        new_caches["prefix"] = []
        for i in range(cfg.n_dense_layers):
            x, c = block_decode(params["prefix"][i], x, caches["prefix"][i],
                                index, cfg, kinds[i], False, memory=memory)
            new_caches["prefix"].append(c)

    layout = _period_layout(cfg)

    def scan_body(x, xs):
        layer_params, layer_cache = xs
        new_cache = {}
        for pos, (kind, use_moe) in enumerate(layout):
            x, c = block_decode(layer_params[f"b{pos}"], x,
                                layer_cache[f"b{pos}"], index, cfg, kind,
                                use_moe, memory=memory)
            new_cache[f"b{pos}"] = c
        return x, new_cache

    x, new_layer_caches = jax.lax.scan(
        scan_body, x, (params["layers"], caches["layers"]),
        unroll=cfg.scan_unroll)
    new_caches["layers"] = new_layer_caches
    _, norm = make_norm(cfg.norm)
    h = norm(params["final_norm"], x)[:, 0]         # (B, d)
    logits = h.astype(jnp.float32) @ lm_head_weight(params, cfg).astype(jnp.float32)
    return logits, new_caches
