"""Unified LM assembler: every assigned architecture is this module with a
different ``ModelConfig``.

Layer stacking: the config's ``pattern`` (period of LayerSpecs) is repeated
``n_repeats`` times; per-position parameters are stacked over repeats and the
stack runs as one ``lax.scan`` — compact HLO even for 61-layer MoEs under
512-way SPMD, with heterogeneous periods (jamba 1 attn : 7 mamba, gemma2
local/global) unrolled only within the period.

Modes:
  * train   — full-sequence forward, CE loss (+ MoE aux), for ``train_step``
  * prefill — full sequence, returns last-position logits + filled caches
  * decode  — one token against the cache (``serve_step``)

Distribution is injected through ``RunContext``: activation sharding
constraints at block boundaries, expert-parallel shard_map MoE, and cache
sharding via the launch layer.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import decode_attention
from repro.models.flash_attention import flash_attention
from repro.models.common import (
    DTYPES,
    apply_rope,
    cross_entropy,
    dense_init,
    embed_init,
    rms_norm,
    softcap,
)
from repro.parallel.sharding import RunContext, constrain

__all__ = ["init_params", "forward", "init_cache", "loss_fn", "Model", "build"]


_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def _experts_padded(cfg: ModelConfig, ep: int = 16) -> int:
    """Experts padded up so EP over the model axis always divides (granite's
    40 experts -> 48; dummies get zero tokens via the router)."""
    return -(-cfg.n_experts // ep) * ep


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_attn(key, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.head_dim
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, cfg.n_heads * hd), dt),
        "wk": dense_init(ks[1], (d, cfg.n_kv_heads * hd), dt),
        "wv": dense_init(ks[2], (d, cfg.n_kv_heads * hd), dt),
        "wo": dense_init(ks[3], (cfg.n_heads * hd, d), dt, fan_in=cfg.n_heads * hd),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    return p


def _init_mlp(key, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(ks[0], (d, f), dt),
        "w_down": dense_init(ks[1], (f, d), dt, fan_in=f),
    }
    if cfg.mlp_gated:
        p["w_gate"] = dense_init(ks[2], (d, f), dt)
    return p


def _init_moe(key, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.expert_d_ff
    e_pad = _experts_padded(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    return moe_mod.MoEWeights(
        router=dense_init(ks[0], (d, cfg.n_experts), jnp.float32),
        w_gate=dense_init(ks[1], (e_pad, d, f), dt) if cfg.mlp_gated else None,
        w_up=dense_init(ks[2], (e_pad, d, f), dt),
        w_down=dense_init(ks[3], (e_pad, f, d), dt, fan_in=f),
    )


def _init_block(key, cfg: ModelConfig, spec: LayerSpec):
    dt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    ks = jax.random.split(key, 2)
    p: dict[str, Any] = {"pre_mixer_norm": jnp.zeros((d,), dt)}
    if spec.mixer == "attn":
        p["attn"] = _init_attn(ks[0], cfg)
    elif spec.mixer == "mamba":
        p["mamba"] = ssm_mod.init_mamba_params(ks[0], cfg)
    if spec.ffn != "none":
        p["pre_ffn_norm"] = jnp.zeros((d,), dt)
        if spec.ffn == "moe":
            p["moe"] = _init_moe(ks[1], cfg)
        else:
            p["mlp"] = _init_mlp(ks[1], cfg)
    if cfg.post_norm:
        p["post_mixer_norm"] = jnp.zeros((d,), dt)
        if spec.ffn != "none":
            p["post_ffn_norm"] = jnp.zeros((d,), dt)
    return p


def init_params(key: jax.Array, cfg: ModelConfig):
    dt = jnp.dtype(cfg.param_dtype)
    k_embed, k_head, k_blocks = jax.random.split(key, 3)
    params: dict[str, Any] = {
        "embed": embed_init(k_embed, (cfg.vocab_size, cfg.d_model), dt),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab_size), dt)

    blocks = []
    for i, spec in enumerate(cfg.pattern):
        keys = jax.random.split(jax.random.fold_in(k_blocks, i), cfg.n_repeats)
        blocks.append(jax.vmap(lambda k, s=spec: _init_block(k, cfg, s))(keys))
    params["blocks"] = blocks
    return params


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


def _attn_apply(p, x, cfg: ModelConfig, ctx: RunContext, spec: LayerSpec,
                positions, cache, mode: str, cur_len):
    B, S, _ = x.shape
    hd, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = (x @ p["wq"]).reshape(B, S, hq, hd)
    k = (x @ p["wk"]).reshape(B, S, hkv, hd)
    v = (x @ p["wv"]).reshape(B, S, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    # Attention sharding policy over the model axis (DESIGN.md §6):
    #   1. KV-head TP   when n_kv_heads divides it (phi3, hubert),
    #   2. GQA-group TP when n_heads/n_kv_heads divides it (glm4),
    #   3. context parallelism (query-sequence sharding) otherwise —
    #      k/v replicate across the model axis, dk/dv psum back.
    tsize = ctx.axis_size(ctx.tp_axis)
    baxes = ctx.dp_axes if ctx.mesh is not None else None
    kv_ax = g_ax = qseq_ax = None
    if baxes is not None and tsize > 1 and S > 1:
        if hkv % tsize == 0:
            kv_ax = ctx.tp_axis
            q = constrain(q, ctx, P(ctx.dp_axes, None, ctx.tp_axis, None))
            k = constrain(k, ctx, P(ctx.dp_axes, None, ctx.tp_axis, None))
            v = constrain(v, ctx, P(ctx.dp_axes, None, ctx.tp_axis, None))
        elif (hq // hkv) % tsize == 0:
            g_ax = ctx.tp_axis
            q = constrain(q, ctx, P(ctx.dp_axes, None, ctx.tp_axis, None))
        else:
            qseq_ax = ctx.tp_axis
            q = constrain(q, ctx, P(ctx.dp_axes, ctx.tp_axis, None, None))
            k = constrain(k, ctx, P(ctx.dp_axes, None, None, None))
            v = constrain(v, ctx, P(ctx.dp_axes, None, None, None))

    new_cache = cache
    if mode == "train":
        out = flash_attention(q, k, v, cfg.causal, spec.window,
                              cfg.attn_softcap, 512, 0, baxes, kv_ax, g_ax,
                              qseq_ax)
    elif mode == "prefill":
        kc = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, 0, 0, 0))
        new_cache = {"k": kc, "v": vc}
        out = flash_attention(q, k, v, cfg.causal, spec.window,
                              cfg.attn_softcap, 512, 0, baxes, kv_ax, g_ax,
                              qseq_ax)
    else:  # decode: insert at cur_len (scalar or per-slot), attend over cache
        cur = jnp.asarray(cur_len)
        if cur.ndim == 0:
            kc = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, cur_len, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, cur_len, 0, 0))
        else:  # continuous batching: per-slot write positions
            rows = jnp.arange(B)
            kc = cache["k"].at[rows, cur].set(k[:, 0].astype(cache["k"].dtype))
            vc = cache["v"].at[rows, cur].set(v[:, 0].astype(cache["v"].dtype))
        new_cache = {"k": kc, "v": vc}
        out = decode_attention(q, kc, vc, cur + 1, window=spec.window,
                               softcap_val=cfg.attn_softcap, q_pos=cur)
    out = out.reshape(B, S, hq * hd)
    return out @ p["wo"], new_cache


def _moe_apply(p: moe_mod.MoEWeights, x, cfg: ModelConfig, ctx: RunContext, mode: str):
    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    act = _ACTS[cfg.mlp_activation]
    if ctx.ep and ctx.mesh is not None:
        all_axes = tuple(ctx.mesh.axis_names)
        wspec = moe_mod.MoEWeights(
            router=P(None, None),
            w_gate=P(ctx.tp_axis, None, None) if p.w_gate is not None else None,
            w_up=P(ctx.tp_axis, None, None),
            w_down=P(ctx.tp_axis, None, None),
        )
        if mode == "train" or mode == "prefill":
            fn = partial(moe_mod.moe_expert_parallel, top_k=cfg.top_k, act=act,
                         axis_name=ctx.tp_axis, capacity_factor=cfg.capacity_factor)
            tok_spec = P(all_axes, None)
        else:
            fn = partial(moe_mod.moe_expert_parallel_gathered, top_k=cfg.top_k,
                         act=act, axis_name=ctx.tp_axis,
                         capacity_factor=cfg.capacity_factor)
            # decode: a handful of tokens; replicate over DP when the token
            # count can't shard (long_500k decodes batch=1)
            dp_size = 1
            for a in ctx.dp_axes:
                dp_size *= ctx.axis_size(a)
            tok_spec = (P(ctx.dp_axes, None) if (B * S) % max(dp_size, 1) == 0
                        and dp_size > 1 else P(None, None))
        def body(xx, ww):
            yy, aux = fn(xx, ww)
            # replicate the aux loss across every mesh axis (shard_map's
            # out_spec P() demands full replication).  The gathered decode
            # path computes the router identically on every model shard, so
            # aux is invarying over tp — pvary before the global pmean.
            missing = tuple(a for a in all_axes
                            if a not in jax.typeof(aux).vma)
            if missing:
                aux = jax.lax.pvary(aux, missing)
            return yy, jax.lax.pmean(aux, all_axes)

        # the vma checker stays on: the pvary above satisfies it
        y2, aux = jax.shard_map(
            body,
            mesh=ctx.mesh,
            in_specs=(tok_spec, wspec),
            out_specs=(tok_spec, P()),
        )(x2, p)
    else:
        y2, aux = moe_mod.moe_dense_sort(x2, p, cfg.top_k, act)
    return y2.reshape(B, S, d), aux


def _mlp_apply(p, x, cfg: ModelConfig, ctx: RunContext):
    act = _ACTS[cfg.mlp_activation]
    up = x @ p["w_up"]
    if cfg.mlp_gated:
        up = act(x @ p["w_gate"]) * up
    else:
        up = act(up)
    up = constrain(up, ctx, P(ctx.dp_axes, None, ctx.tp_axis))
    return up @ p["w_down"]


def _block_apply(p, spec: LayerSpec, x, cfg: ModelConfig, ctx: RunContext,
                 positions, cache, mode: str, cur_len):
    x = constrain(x, ctx, P(ctx.dp_axes, ctx.seq_axis, None))
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache

    # sequence mixer
    if spec.mixer == "attn":
        h = rms_norm(x, p["pre_mixer_norm"], cfg.norm_eps)
        h, new_cache = _attn_apply(p["attn"], h, cfg, ctx, spec, positions,
                                   cache, mode, cur_len)
        if cfg.post_norm:
            h = rms_norm(h, p["post_mixer_norm"], cfg.norm_eps)
        x = x + h
    elif spec.mixer == "mamba":
        h = rms_norm(x, p["pre_mixer_norm"], cfg.norm_eps)
        if mode == "decode":
            h, new_cache = ssm_mod.mamba_decode_step(p["mamba"], h, cfg, cache)
        else:
            use_cache = cache if mode == "prefill" else None
            h, new_cache = ssm_mod.mamba_block(p["mamba"], h, cfg,
                                               cache=use_cache,
                                               use_pallas=ctx.use_pallas)
            if mode == "train":
                new_cache = cache
        if cfg.post_norm:
            h = rms_norm(h, p["post_mixer_norm"], cfg.norm_eps)
        x = x + h

    # channel mixer
    if spec.ffn != "none":
        h = rms_norm(x, p["pre_ffn_norm"], cfg.norm_eps)
        if spec.ffn == "moe":
            h, aux = _moe_apply(p["moe"], h, cfg, ctx, mode)
        else:
            h = _mlp_apply(p["mlp"], h, cfg, ctx)
        if cfg.post_norm:
            h = rms_norm(h, p["post_ffn_norm"], cfg.norm_eps)
        x = x + h
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Stack + heads
# ---------------------------------------------------------------------------


def _apply_stack(params, x, cfg: ModelConfig, ctx: RunContext, positions,
                 caches, mode: str, cur_len):
    """scan over period repeats; period unrolled inside the body."""

    def body(carry, xs):
        x, aux_sum = carry
        params_r, cache_r = xs
        new_caches = []
        for i, spec in enumerate(cfg.pattern):
            x, nc, aux = _block_apply(params_r[i], spec, x, cfg, ctx, positions,
                                      cache_r[i], mode, cur_len)
            new_caches.append(nc)
        return (x, aux_sum + aux), new_caches

    if ctx.remat != "none":
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if ctx.remat == "dots" else jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(body, policy=policy)

    (x, aux), new_caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (params["blocks"], caches)
    )
    return x, aux, new_caches


def _embed_inputs(params, batch, cfg: ModelConfig, ctx: RunContext, offset):
    """Token/frontend embedding; returns (x, positions)."""
    if cfg.frontend == "audio_stub":
        x = batch["features"].astype(DTYPES[cfg.compute_dtype])
    elif cfg.frontend == "vision_stub" and "image_embeds" in batch:
        # prefill/train: prepend the stub patch embeddings; decode steps
        # carry only new text tokens (the image lives in the KV cache)
        tok = jnp.take(params["embed"], batch["tokens"], axis=0)
        img = batch["image_embeds"].astype(tok.dtype)
        x = jnp.concatenate([img, tok], axis=1)
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    x = x.astype(DTYPES[cfg.compute_dtype])
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    B, S = x.shape[:2]
    # offset: scalar or per-batch (B,) (continuous batching decodes slots at
    # different sequence positions)
    off = jnp.reshape(jnp.asarray(offset), (-1, 1))
    positions = jnp.broadcast_to(off + jnp.arange(S)[None], (B, S))
    return x, positions


def _head(params, x, cfg: ModelConfig, ctx: RunContext):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ w.astype(x.dtype)
    if cfg.final_softcap is not None:
        logits = softcap(logits, cfg.final_softcap)
    return constrain(logits, ctx, P(ctx.dp_axes, None, ctx.tp_axis))


def forward(params, batch, cfg: ModelConfig, ctx: RunContext, mode: str,
            caches=None, cur_len=0):
    """Returns:
       train   -> (logits, aux)
       prefill -> (last_logits, caches)
       decode  -> (logits, caches)
    """
    x, positions = _embed_inputs(params, batch, cfg, ctx,
                                 offset=cur_len if mode == "decode" else 0)
    if caches is None:
        caches = _dummy_caches(cfg)
    x, aux, new_caches = _apply_stack(params, x, cfg, ctx, positions, caches,
                                      mode, cur_len)
    if mode == "train":
        return _head(params, x, cfg, ctx), aux
    if mode == "prefill":
        return _head(params, x[:, -1:], cfg, ctx)[:, 0], new_caches
    return _head(params, x, cfg, ctx), new_caches


def _dummy_caches(cfg: ModelConfig):
    """Cache pytree with no leaves (train mode) — keeps scan xs structure."""
    return [
        jax.tree.map(lambda _: None, {})  # placeholder per position
        for _ in cfg.pattern
    ]


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.float32):
    """Stacked (n_repeats-leading) caches per period position."""
    caches = []
    for spec in cfg.pattern:
        if spec.mixer == "attn":
            shape = (cfg.n_repeats, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
            caches.append({"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)})
        elif spec.mixer == "mamba":
            one = ssm_mod.init_mamba_cache(cfg, batch, dtype)
            caches.append(jax.tree.map(
                lambda a: jnp.zeros((cfg.n_repeats, *a.shape), a.dtype), one))
        else:
            caches.append({})
    return caches


def _chunked_ce(params, x, labels, cfg: ModelConfig, ctx: RunContext,
                target_chunk: int = 256):
    """CE without materialising (S, vocab) logits: the head + logsumexp run
    per sequence chunk under jax.checkpoint, so the backward recomputes one
    chunk of logits at a time.  At 256k-vocab × 1M-token cells this is the
    difference between ~4 GB and ~0.25 GB of per-device head activations."""
    B, S, d = x.shape
    n_chunks = max(1, S // max(1, min(target_chunk, S)))
    while S % n_chunks:
        n_chunks -= 1
    cs = S // n_chunks
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    # NOTE: do NOT constrain w to be data-replicated here — that forces the
    # dW matmul to run on all-gathered GLOBAL-batch dlogits (measured:
    # 10.5 TF replicated work per CE chunk on granite).  Leaving w FSDP-
    # sharded keeps dW a batch-partial matmul + reduce-scatter, at the cost
    # of a small per-chunk weight gather.

    def body(carry, xs):
        xc, lc = xs                              # (B, cs, d), (B, cs)
        h = rms_norm(xc, params["final_norm"], cfg.norm_eps)
        logits = h @ w.astype(h.dtype)
        if cfg.final_softcap is not None:
            logits = softcap(logits, cfg.final_softcap)
        logits = constrain(logits, ctx, P(ctx.dp_axes, None, ctx.tp_axis))
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - gold), None

    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    xs = jnp.moveaxis(x.reshape(B, n_chunks, cs, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, n_chunks, cs), 1, 0)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    return total / (B * S)


def loss_fn(params, batch, cfg: ModelConfig, ctx: RunContext):
    """CE on next-token (or encoder targets) + MoE aux; the LM head is
    evaluated chunk-by-chunk (never a full (S, vocab) logits tensor)."""
    x, positions = _embed_inputs(params, batch, cfg, ctx, offset=0)
    x, aux, _ = _apply_stack(params, x, cfg, ctx, positions,
                             _dummy_caches(cfg), "train", 0)
    if cfg.frontend == "vision_stub":
        n_img = batch["image_embeds"].shape[1]
        x = x[:, n_img:, :]
    if cfg.causal:
        x = x[:, :-1, :]
        labels = batch["labels"][:, 1:]
    else:
        labels = batch["labels"]
    loss = _chunked_ce(params, x, labels, cfg, ctx)
    return loss + cfg.router_aux_coef * aux, {"ce": loss, "aux": aux}


# ---------------------------------------------------------------------------
# Public build API
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    def init(self, key, ctx: RunContext | None = None):
        return init_params(key, self.cfg)

    def loss(self, params, batch, ctx: RunContext):
        return loss_fn(params, batch, self.cfg, ctx)

    def prefill(self, params, batch, caches, ctx: RunContext):
        return forward(params, batch, self.cfg, ctx, "prefill", caches)

    def decode(self, params, batch, caches, cur_len, ctx: RunContext):
        return forward(params, batch, self.cfg, ctx, "decode", caches, cur_len)

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        return init_cache(self.cfg, batch, max_len, dtype)


def build(cfg: ModelConfig) -> Model:
    return Model(cfg)
