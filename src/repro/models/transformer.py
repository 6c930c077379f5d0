"""Generic decoder-family LM covering all 10 assigned architectures.

One ``init_params`` / ``forward`` / ``decode_step`` triple drives every
family (dense / vlm / audio / moe / ssm / hybrid).  Layers are stacked and
executed under ``lax.scan`` so HLO size — and therefore dry-run compile time
for the 88/94-layer configs — is O(1) in depth.  Per-layer variation
(gemma3's 5:1 local:global windows) rides along as scanned flag arrays.

The IMAGine engine plugs in through ``quantize_params`` + the ``eng``
argument: every linear then reads b-bit packed weights (b/8 bytes/weight of
HBM traffic) — the paper's PIM GEMV as the serving fast path.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import EngineConfig, ModelConfig
from repro.dist.hints import shard_batch_seq
from repro.dist.sharding import _ROW as _ROW_PARALLEL
from repro.engine import as_plan, pack_linear, resolve_attn_backend
from repro.models.attention import (
    FLASH_THRESHOLD,
    attend_decode,
    attend_decode_quant,
    attend_dense,
    attend_flash,
    attend_local_gather,
    attend_paged_decode,
    attend_paged_prefill,
)
from repro.models.layers import (
    apply_rope,
    dense,
    init_embedding,
    init_linear,
    rms_norm,
    swiglu,
)
from repro.models.moe import init_moe, moe_block
from repro.models.ssm import init_ssm, ssm_decode_step, ssm_forward

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_attn(key, cfg: ModelConfig, dtype):
    d, dh = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], d, hq * dh, dtype, bias=cfg.qkv_bias),
        "wk": init_linear(ks[1], d, hkv * dh, dtype, bias=cfg.qkv_bias),
        "wv": init_linear(ks[2], d, hkv * dh, dtype, bias=cfg.qkv_bias),
        "wo": init_linear(ks[3], hq * dh, d, dtype),
    }


def _init_mlp(key, cfg: ModelConfig, dtype, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    out = {
        "w_up": init_linear(ks[1], d, f, dtype),
        "w_down": init_linear(ks[2], f, d, dtype),
    }
    if cfg.mlp_gated:
        out["w_gate"] = init_linear(ks[0], d, f, dtype)
    return out


def _init_block(key, cfg: ModelConfig, dtype):
    """One scanned layer for the cfg's family."""
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    if cfg.family in ("dense", "vlm", "audio"):
        return {
            "ln1": jnp.zeros((d,), dtype),
            "attn": _init_attn(ks[0], cfg, dtype),
            "ln2": jnp.zeros((d,), dtype),
            "mlp": _init_mlp(ks[1], cfg, dtype),
        }
    if cfg.family == "moe":
        return {
            "ln1": jnp.zeros((d,), dtype),
            "attn": _init_attn(ks[0], cfg, dtype),
            "ln2": jnp.zeros((d,), dtype),
            "moe": init_moe(ks[1], cfg, dtype),
        }
    if cfg.family in ("ssm", "hybrid"):
        return {
            "ln1": jnp.zeros((d,), dtype),
            "ssm": init_ssm(ks[0], cfg, dtype),
        }
    raise ValueError(cfg.family)


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dtype = _dtype(cfg)
    k_emb, k_layers, k_shared, k_head = jax.random.split(key, 4)
    if cfg.family == "audio":
        emb = jax.vmap(
            lambda k: init_embedding(k, cfg.vocab_size, cfg.d_model, dtype)
        )(jax.random.split(k_emb, cfg.n_codebooks))
    else:
        emb = init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dtype)

    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(lambda k: _init_block(k, cfg, dtype))(layer_keys)

    params: Params = {
        "embed": emb,
        "layers": layers,
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if cfg.family == "hybrid" and cfg.attn_every:
        ks = jax.random.split(k_shared, 2)
        params["shared_attn"] = {
            "ln1": jnp.zeros((cfg.d_model,), dtype),
            "attn": _init_attn(ks[0], cfg, dtype),
            "ln2": jnp.zeros((cfg.d_model,), dtype),
            "mlp": _init_mlp(ks[1], cfg, dtype),
        }
    if not cfg.tie_embeddings:
        out_dim = cfg.vocab_size * cfg.n_codebooks
        params["lm_head"] = init_linear(k_head, cfg.d_model, out_dim, dtype)
    return params


# ---------------------------------------------------------------------------
# shared block application
# ---------------------------------------------------------------------------


def _layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """(L,) per-layer sliding window (0 = global/full attention)."""
    win = [0 if cfg.is_global_layer(i) else cfg.sliding_window
           for i in range(cfg.n_layers)]
    return jnp.asarray(win, jnp.int32)


def _attn_apply(p, x, positions, cfg, eng, window, *, use_flash: bool,
                local_gather: bool = False):
    """Full-sequence attention sub-block.  Returns (out, (k, v))."""
    b, s, d = x.shape
    dh, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q = dense(p["attn"]["wq"], h, eng).reshape(b, s, hq, dh)
    k = dense(p["attn"]["wk"], h, eng).reshape(b, s, hkv, dh)
    v = dense(p["attn"]["wv"], h, eng).reshape(b, s, hkv, dh)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if local_gather and isinstance(window, int) and window > 0:
        o = attend_local_gather(q, k, v, positions, window)
    elif use_flash:
        o = attend_flash(q, k, v, positions, window)
    else:
        o = attend_dense(q, k, v, positions, positions, window)
    o = dense(p["attn"]["wo"], o.reshape(b, s, hq * dh), eng)
    return x + o, (k, v)


def _mlp_apply(p, x, cfg, eng):
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    return x + swiglu(p["mlp"], h, eng)


def _moe_apply(p, x, cfg, eng):
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    y, aux = moe_block(p["moe"], h, cfg, eng)
    return x + y, aux


def _ssm_apply(p, x, cfg, eng):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    return x + ssm_forward(p["ssm"], h, cfg, eng)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def embed_inputs(params: Params, batch: Dict[str, jnp.ndarray],
                 cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (x (B,S,D), positions (B,S))."""
    if cfg.family == "audio":
        toks = batch["tokens"]                       # (B, S, K)
        x = sum(
            jnp.take(params["embed"][k], toks[..., k], axis=0)
            for k in range(cfg.n_codebooks)
        )
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)  # (B,S,D)
    if cfg.family == "vlm" and "img_embeds" in batch:
        img = batch["img_embeds"].astype(x.dtype)    # (B, S_img, D)
        x = jnp.concatenate([img, x], axis=1)
    b, s = x.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return x, positions


def _lm_logits(params, x, cfg, eng):
    with jax.named_scope("_lm_logits"):
        return _lm_logits_inner(params, x, cfg, eng)


def _lm_logits_inner(params, x, cfg, eng):
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(h.dtype))
    else:
        logits = dense(params["lm_head"], h, eng)
    if cfg.family == "audio":
        b, s = logits.shape[:2]
        logits = logits.reshape(b, s, cfg.n_codebooks, cfg.vocab_size)
    return logits


def forward(
    params: Params,
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    eng: Optional[EngineConfig] = None,
    remat: str = "block",
    local_gather: bool = False,
    return_hidden: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward.  Returns (logits, aux_loss) — or
    (hidden, aux_loss) with ``return_hidden`` (the chunked-CE train path
    computes the LM head per sequence chunk instead of materializing the
    full (B, S, vocab) logits)."""
    eng = as_plan(eng)  # EngineConfig | EnginePlan | None -> resolved plan
    x, positions = embed_inputs(params, batch, cfg)
    x = shard_batch_seq(x)
    s = x.shape[1]
    use_flash = s >= FLASH_THRESHOLD

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        windows = _layer_windows(cfg)

        def body(carry, xs):
            x, aux = carry
            lp, win = xs
            x, _ = _attn_apply(lp, x, positions, cfg, eng, win,
                               use_flash=use_flash,
                               local_gather=local_gather)
            if cfg.family == "moe":
                x, a = _moe_apply(lp, x, cfg, eng)
                aux = aux + a
            else:
                x = _mlp_apply(lp, x, cfg, eng)
            return (x, aux), None

        if local_gather and cfg.sliding_window > 0 and cfg.global_every > 0:
            # static local/global split cannot ride a traced window flag;
            # run layers unscanned in groups (hillclimb-C variant).
            aux = jnp.zeros((), jnp.float32)
            for i in range(cfg.n_layers):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                win = 0 if cfg.is_global_layer(i) else cfg.sliding_window
                x, _ = _attn_apply(lp, x, positions, cfg, eng, win,
                                   use_flash=use_flash, local_gather=True)
                x = _mlp_apply(lp, x, cfg, eng)
        else:
            fn = jax.checkpoint(body) if remat != "none" else body
            (x, aux), _ = jax.lax.scan(
                fn, (x, jnp.zeros((), jnp.float32)),
                (params["layers"], windows),
            )

    elif cfg.family == "ssm":

        def body(carry, lp):
            return _ssm_apply(lp, carry, cfg, eng), None

        fn = jax.checkpoint(body) if remat != "none" else body
        x, _ = jax.lax.scan(fn, x, params["layers"])
        aux = jnp.zeros((), jnp.float32)

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        every = cfg.attn_every

        def body(carry, xs):
            x = carry
            lp, idx = xs
            x = _ssm_apply(lp, x, cfg, eng)

            def with_attn(x):
                x, _ = _attn_apply(shared, x, positions, cfg, eng, 0,
                                   use_flash=use_flash)
                return _mlp_apply(shared, x, cfg, eng)

            x = jax.lax.cond((idx + 1) % every == 0, with_attn,
                             lambda x: x, x)
            return x, None

        fn = jax.checkpoint(body) if remat != "none" else body
        idxs = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        x, _ = jax.lax.scan(fn, x, (params["layers"], idxs))
        aux = jnp.zeros((), jnp.float32)
    else:
        raise ValueError(cfg.family)

    if cfg.family == "vlm" and "img_embeds" in batch:
        x = x[:, batch["img_embeds"].shape[1] :]
    if return_hidden:
        return x, aux
    logits = _lm_logits(params, x, cfg, eng)
    return logits, aux


def loss_fn(logits: jnp.ndarray, labels: jnp.ndarray,
            aux: jnp.ndarray = 0.0) -> jnp.ndarray:
    """Token-mean cross entropy (+ router aux).  labels: int, same leading
    shape as logits minus the vocab axis."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + aux


def chunked_ce(params: Params, hidden: jnp.ndarray, labels: jnp.ndarray,
               cfg: ModelConfig, eng: Optional[EngineConfig] = None,
               chunk: int = 512, aux: jnp.ndarray = 0.0) -> jnp.ndarray:
    """Cross entropy with the LM head applied per sequence chunk.

    Peak live logits are (B, chunk, V) instead of (B, S, V) — the standard
    large-vocab memory optimization (MaxText-style); numerically identical
    to ``loss_fn(_lm_logits(hidden))``.
    """
    eng = as_plan(eng)
    b, s = hidden.shape[:2]
    chunk = min(chunk, s)
    if s % chunk != 0:
        return loss_fn(_lm_logits(params, hidden, cfg, eng), labels, aux)
    nc = s // chunk
    hs = hidden.reshape(b, nc, chunk, -1).transpose(1, 0, 2, 3)
    ls = labels.reshape((b, nc, chunk) + labels.shape[2:]).swapaxes(0, 1)

    def body(acc, xs):
        hc, lc = xs
        logits = _lm_logits(params, hc, cfg, eng)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, lc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(nll), None

    total, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros(()), (hs, ls))
    n_tok = labels.size
    return total / n_tok + aux


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None, split_local: bool = False,
               stacked: bool = True, kv_bits: int = 0) -> Params:
    """Decode cache.

    ``split_local=True`` (gemma3 hillclimb) allocates window-capped ring
    buffers for local layers instead of full-length.

    ``stacked=False`` stores per-layer caches as tuples instead of one
    (L, ...) array: the decode step then runs an unrolled layer loop where
    every cache update is an in-place scatter on its own (donated) buffer —
    no stacked loop-carry, which on TPU avoids spurious cache copies and is
    the production decode layout.  The dry-run serve cells use this.

    ``kv_bits=8`` (beyond-paper: the IMAGine bit-plane idea applied to the
    cache) stores K/V as int8 with per-(token, head) scales — halving the
    dominant decode-memory term vs bf16.
    """
    dtype = dtype or _dtype(cfg)
    if kv_bits:
        dtype = jnp.int8
    dh, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
    cache: Params = {"pos": jnp.zeros((batch,), jnp.int32)}

    def maybe_split(arr):
        if stacked:
            return arr
        return tuple(arr[i] for i in range(arr.shape[0]))

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        if split_local and cfg.sliding_window > 0 and cfg.global_every > 0:
            n_glob = sum(cfg.is_global_layer(i) for i in range(cfg.n_layers))
            n_loc = cfg.n_layers - n_glob
            w = cfg.sliding_window
            cache["k_global"] = maybe_split(
                jnp.zeros((n_glob, batch, max_len, hkv, dh), dtype))
            cache["v_global"] = maybe_split(
                jnp.zeros((n_glob, batch, max_len, hkv, dh), dtype))
            cache["k_local"] = maybe_split(
                jnp.zeros((n_loc, batch, w, hkv, dh), dtype))
            cache["v_local"] = maybe_split(
                jnp.zeros((n_loc, batch, w, hkv, dh), dtype))
        else:
            shape = (cfg.n_layers, batch, max_len, hkv, dh)
            cache["k"] = maybe_split(jnp.zeros(shape, dtype))
            cache["v"] = maybe_split(jnp.zeros(shape, dtype))
            if kv_bits:
                sshape = (cfg.n_layers, batch, max_len, hkv)
                cache["k_scale"] = maybe_split(jnp.zeros(sshape, jnp.bfloat16))
                cache["v_scale"] = maybe_split(jnp.zeros(sshape, jnp.bfloat16))
    elif cfg.family in ("ssm", "hybrid"):
        conv_ch = cfg.d_inner + 2 * cfg.ssm_state
        cache["conv"] = maybe_split(jnp.zeros(
            (cfg.n_layers, batch, cfg.conv_width - 1, conv_ch), dtype))
        cache["h"] = maybe_split(jnp.zeros(
            (cfg.n_layers, batch, cfg.n_ssm_heads, cfg.ssm_head_dim,
             cfg.ssm_state), jnp.float32))
        if cfg.family == "hybrid" and cfg.attn_every:
            sites = cfg.n_layers // cfg.attn_every
            cache["k"] = maybe_split(
                jnp.zeros((sites, batch, max_len, hkv, dh), dtype))
            cache["v"] = maybe_split(
                jnp.zeros((sites, batch, max_len, hkv, dh), dtype))
    return cache


# ---------------------------------------------------------------------------
# prefill: forward + cache population (the serving prompt phase)
# ---------------------------------------------------------------------------


def prefill(
    params: Params,
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    cache: Params,
    eng: Optional[EngineConfig] = None,
) -> Tuple[jnp.ndarray, Params]:
    """Run the prompt through the model, filling the decode cache.

    Returns (last-token logits (B,1,V...), cache).  The compute is the same
    chunked-flash forward as training (no S^2 blocks); K/V per layer are
    collected as scan outputs and written into the cache.
    """
    eng = as_plan(eng)
    x, positions = embed_inputs(params, batch, cfg)
    x = shard_batch_seq(x)
    b, s = x.shape[:2]
    use_flash = s >= FLASH_THRESHOLD
    new_cache = dict(cache)
    t = None

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        t = cache["k"].shape[2]
        windows = _layer_windows(cfg)

        def body(carry, xs):
            x = carry
            lp, win = xs
            x, (k, v) = _attn_apply(lp, x, positions, cfg, eng, win,
                                    use_flash=use_flash)
            if cfg.family == "moe":
                x, _ = _moe_apply(lp, x, cfg, eng)
            else:
                x = _mlp_apply(lp, x, cfg, eng)
            return x, (k, v)

        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], windows))
        pad = t - s
        new_cache["k"] = jnp.pad(
            ks.astype(cache["k"].dtype), ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        new_cache["v"] = jnp.pad(
            vs.astype(cache["v"].dtype), ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))

    elif cfg.family in ("ssm", "hybrid"):
        from repro.models.ssm import _ssm_run

        shared = params.get("shared_attn")
        every = cfg.attn_every
        if "k" in cache:
            t = cache["k"].shape[2]

        def body(carry, xs):
            x, ck_all, cv_all = carry
            lp, idx, conv0 = xs
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            y, conv_state, h_state = _ssm_run(
                lp["ssm"], h, cfg, eng, conv_state=None, h0=None)
            x = x + y
            if shared is not None:
                site = (idx + 1) // every - 1

                def with_attn(op):
                    x, ck_all, cv_all = op
                    x, (k, v) = _attn_apply(shared, x, positions, cfg, eng, 0,
                                            use_flash=use_flash)
                    x = _mlp_apply(shared, x, cfg, eng)
                    pad = ck_all.shape[2] - s
                    kp = jnp.pad(k.astype(ck_all.dtype),
                                 ((0, 0), (0, pad), (0, 0), (0, 0)))
                    vp = jnp.pad(v.astype(cv_all.dtype),
                                 ((0, 0), (0, pad), (0, 0), (0, 0)))
                    ck_all = jax.lax.dynamic_update_index_in_dim(ck_all, kp, site, 0)
                    cv_all = jax.lax.dynamic_update_index_in_dim(cv_all, vp, site, 0)
                    return x, ck_all, cv_all

                x, ck_all, cv_all = jax.lax.cond(
                    (idx + 1) % every == 0, with_attn, lambda op: op,
                    (x, ck_all, cv_all))
            return (x, ck_all, cv_all), (conv_state, h_state)

        idxs = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        if "k" in cache:
            init = (x, cache["k"], cache["v"])
        else:
            dh, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
            dummy = jnp.zeros((1, b, 1, max(hkv, 1), max(dh, 1)), x.dtype)
            init = (x, dummy, dummy)
        (x, nck, ncv), (convs, hs) = jax.lax.scan(
            body, init, (params["layers"], idxs, cache["conv"]))
        new_cache["conv"] = convs.astype(cache["conv"].dtype)
        new_cache["h"] = hs
        if "k" in cache:
            new_cache["k"], new_cache["v"] = nck, ncv
    else:
        raise ValueError(cfg.family)

    if cfg.family == "vlm" and "img_embeds" in batch:
        x = x[:, batch["img_embeds"].shape[1] :]
    new_cache["pos"] = jnp.full((b,), s, jnp.int32)
    logits = _lm_logits(params, x[:, -1:], cfg, eng)
    return logits, new_cache


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------


def _attn_decode_apply(p, x, cache_k, cache_v, pos, cfg, eng, window,
                       scales=None):
    """One cached-attention sub-block for a single new token.

    cache_k/v: (B, T, Hkv, Dh); pos: (B,) position of the new token.
    ``scales``: (k_scale, v_scale) (B, T, Hkv) when the cache is int8
    (beyond-paper quantized-KV mode).  Returns (x, new_k, new_v[, scales]).
    """
    b, _, d = x.shape
    dh, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q = dense(p["attn"]["wq"], h, eng).reshape(b, 1, hq, dh)
    k = dense(p["attn"]["wk"], h, eng).reshape(b, 1, hkv, dh)
    v = dense(p["attn"]["wv"], h, eng).reshape(b, 1, hkv, dh)
    pos2 = pos[:, None]
    q = apply_rope(q, pos2, cfg.rope_theta)
    k = apply_rope(k, pos2, cfg.rope_theta)
    bidx = jnp.arange(b)
    t = cache_k.shape[1]
    slot = jnp.minimum(pos, t - 1)

    if scales is not None:
        # int8 cache: symmetric per-(token, head) quantization at write
        k_sc, v_sc = scales
        kq, ks_new = _quantize_kv(k[:, 0])
        vq, vs_new = _quantize_kv(v[:, 0])
        new_k = cache_k.at[bidx, slot].set(kq)
        new_v = cache_v.at[bidx, slot].set(vq)
        k_sc = k_sc.at[bidx, slot].set(ks_new.astype(k_sc.dtype))
        v_sc = v_sc.at[bidx, slot].set(vs_new.astype(v_sc.dtype))
        o = _attend_decode_quant(q, new_k, new_v, k_sc, v_sc, pos, window)
        o = dense(p["attn"]["wo"], o.reshape(b, 1, hq * dh), eng)
        return x + o, new_k, new_v, (k_sc, v_sc)

    new_k = cache_k.at[bidx, slot].set(k[:, 0].astype(cache_k.dtype))
    new_v = cache_v.at[bidx, slot].set(v[:, 0].astype(cache_v.dtype))
    o = attend_decode(q, new_k, new_v, pos, window)
    o = dense(p["attn"]["wo"], o.reshape(b, 1, hq * dh), eng)
    return x + o, new_k, new_v


def _quantize_kv(val):
    """Symmetric per-(…, head) int8 quantization of a K/V write.

    ``val``: ``(..., Hkv, Dh)`` float -> (int8 of the same shape,
    ``(..., Hkv)`` float scales).
    """
    absmax = jnp.max(jnp.abs(val.astype(jnp.float32)), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    qv = jnp.clip(jnp.round(val.astype(jnp.float32)
                            / scale[..., None]), -127, 127)
    return qv.astype(jnp.int8), scale


# moved to repro.models.attention (shared with the paged read path); the
# underscore name is kept as an alias for existing importers.
_attend_decode_quant = attend_decode_quant


def _attn_decode_apply_ring(p, x, cache_k, cache_v, pos, cfg, eng, window):
    """Ring-buffer variant for window-capped local caches (split_local)."""
    b, _, d = x.shape
    dh, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    w = cache_k.shape[1]
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q = dense(p["attn"]["wq"], h, eng).reshape(b, 1, hq, dh)
    k = dense(p["attn"]["wk"], h, eng).reshape(b, 1, hkv, dh)
    v = dense(p["attn"]["wv"], h, eng).reshape(b, 1, hkv, dh)
    pos2 = pos[:, None]
    q = apply_rope(q, pos2, cfg.rope_theta)
    k = apply_rope(k, pos2, cfg.rope_theta)
    bidx = jnp.arange(b)
    slot = pos % w
    new_k = cache_k.at[bidx, slot].set(k[:, 0].astype(cache_k.dtype))
    new_v = cache_v.at[bidx, slot].set(v[:, 0].astype(cache_v.dtype))
    # ring positions: slot i holds absolute position derived from pos
    ring = jnp.arange(w)[None, :]
    cur_slot = slot[:, None]
    age = (cur_slot - ring) % w                      # 0 = newest
    abs_pos = pos[:, None] - age
    valid = abs_pos >= 0
    scale = dh ** -0.5
    qg = q.reshape(b, hkv, hq // hkv, dh).astype(jnp.float32)
    sc = jnp.einsum("bhgd,bkhd->bhgk", qg, new_k.astype(jnp.float32)) * scale
    sc = jnp.where(valid[:, None, None, :], sc, -1e30)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", pr, new_v.astype(jnp.float32))
    o = o.reshape(b, 1, hq * dh).astype(x.dtype)
    o = dense(p["attn"]["wo"], o, eng)
    return x + o, new_k, new_v


def decode_step(
    params: Params,
    cache: Params,
    tokens: jnp.ndarray,                 # (B, 1) or (B, 1, K) for audio
    cfg: ModelConfig,
    eng: Optional[EngineConfig] = None,
) -> Tuple[jnp.ndarray, Params]:
    """One token of autoregressive decode.  Returns (logits, new_cache)."""
    eng = as_plan(eng)
    pos = cache["pos"]                   # (B,)
    if cfg.family == "audio":
        x = sum(
            jnp.take(params["embed"][k], tokens[..., k], axis=0)
            for k in range(cfg.n_codebooks)
        )
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    new_cache = dict(cache)
    unstacked = isinstance(cache.get("k", cache.get("conv")), tuple)

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        if "k_global" in cache:
            x = _decode_split_local(params, cache, new_cache, x, pos, cfg, eng)
        elif unstacked:
            windows = [0 if cfg.is_global_layer(i) else cfg.sliding_window
                       for i in range(cfg.n_layers)]
            quant_kv = "k_scale" in cache
            nk, nv, nks, nvs = [], [], [], []
            for i in range(cfg.n_layers):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                if quant_kv:
                    x, k_i, v_i, (ks_i, vs_i) = _attn_decode_apply(
                        lp, x, cache["k"][i], cache["v"][i], pos, cfg, eng,
                        windows[i],
                        scales=(cache["k_scale"][i], cache["v_scale"][i]))
                    nks.append(ks_i)
                    nvs.append(vs_i)
                else:
                    x, k_i, v_i = _attn_decode_apply(
                        lp, x, cache["k"][i], cache["v"][i], pos, cfg, eng,
                        windows[i])
                if cfg.family == "moe":
                    x, _ = _moe_apply(lp, x, cfg, eng)
                else:
                    x = _mlp_apply(lp, x, cfg, eng)
                nk.append(k_i)
                nv.append(v_i)
            new_cache["k"], new_cache["v"] = tuple(nk), tuple(nv)
            if quant_kv:
                new_cache["k_scale"] = tuple(nks)
                new_cache["v_scale"] = tuple(nvs)
        else:
            windows = _layer_windows(cfg)

            def body(x, xs):
                lp, win, ck, cv = xs
                x, nk, nv = _attn_decode_apply(lp, x, ck, cv, pos, cfg, eng, win)
                if cfg.family == "moe":
                    x, _ = _moe_apply(lp, x, cfg, eng)
                else:
                    x = _mlp_apply(lp, x, cfg, eng)
                return x, (nk, nv)

            x, (nk, nv) = jax.lax.scan(
                body, x, (params["layers"], windows, cache["k"], cache["v"])
            )
            new_cache["k"], new_cache["v"] = nk, nv

    elif cfg.family in ("ssm", "hybrid"):
        if unstacked:
            x, new_cache = _decode_ssm_unrolled(
                params, cache, new_cache, x, pos, cfg, eng)
            new_cache["pos"] = pos + 1
            logits = _lm_logits(params, x, cfg, eng)
            return logits, new_cache
        shared = params.get("shared_attn")
        every = cfg.attn_every
        attn_cache = [cache.get("k"), cache.get("v")]

        def body(carry, xs):
            x, ck_all, cv_all = carry
            lp, idx, conv, h = xs
            hnorm = rms_norm(x, lp["ln1"], cfg.norm_eps)
            y, new_conv, new_h = ssm_decode_step(
                lp["ssm"], hnorm, cfg, conv, h, eng)
            x = x + y
            if shared is not None:
                site = (idx + 1) // every - 1

                def with_attn(op):
                    x, ck_all, cv_all = op
                    ck = jax.lax.dynamic_index_in_dim(ck_all, site, 0, False)
                    cv = jax.lax.dynamic_index_in_dim(cv_all, site, 0, False)
                    x, nk, nv = _attn_decode_apply(
                        shared, x, ck, cv, pos, cfg, eng, 0)
                    x = _mlp_apply(shared, x, cfg, eng)
                    ck_all = jax.lax.dynamic_update_index_in_dim(
                        ck_all, nk, site, 0)
                    cv_all = jax.lax.dynamic_update_index_in_dim(
                        cv_all, nv, site, 0)
                    return x, ck_all, cv_all

                x, ck_all, cv_all = jax.lax.cond(
                    (idx + 1) % every == 0, with_attn, lambda op: op,
                    (x, ck_all, cv_all))
            return (x, ck_all, cv_all), (new_conv, new_h)

        idxs = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        init = (x, attn_cache[0], attn_cache[1])
        if attn_cache[0] is None:
            dh, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
            dummy = jnp.zeros((1, x.shape[0], 1, hkv, dh), x.dtype)
            init = (x, dummy, dummy)
        (x, nck, ncv), (nconv, nh) = jax.lax.scan(
            body, init, (params["layers"], idxs, cache["conv"], cache["h"])
        )
        new_cache["conv"], new_cache["h"] = nconv, nh
        if "k" in cache:
            new_cache["k"], new_cache["v"] = nck, ncv
    else:
        raise ValueError(cfg.family)

    new_cache["pos"] = pos + 1
    logits = _lm_logits(params, x, cfg, eng)
    return logits, new_cache


def _decode_ssm_unrolled(params, cache, new_cache, x, pos, cfg, eng):
    """Unrolled ssm/hybrid decode over tuple caches (production layout)."""
    shared = params.get("shared_attn")
    every = cfg.attn_every
    nconv, nh = [], []
    nk = list(cache["k"]) if "k" in cache else []
    nv = list(cache["v"]) if "k" in cache else []
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        hnorm = rms_norm(x, lp["ln1"], cfg.norm_eps)
        y, conv_i, h_i = ssm_decode_step(
            lp["ssm"], hnorm, cfg, cache["conv"][i], cache["h"][i], eng)
        x = x + y
        nconv.append(conv_i)
        nh.append(h_i)
        if shared is not None and (i + 1) % every == 0:
            site = (i + 1) // every - 1
            x, k_s, v_s = _attn_decode_apply(
                shared, x, nk[site], nv[site], pos, cfg, eng, 0)
            x = _mlp_apply(shared, x, cfg, eng)
            nk[site], nv[site] = k_s, v_s
    new_cache["conv"], new_cache["h"] = tuple(nconv), tuple(nh)
    if nk:
        new_cache["k"], new_cache["v"] = tuple(nk), tuple(nv)
    return x, new_cache


def _decode_split_local(params, cache, new_cache, x, pos, cfg, eng):
    """Unscanned decode for the split local/global cache layout (gemma3
    hillclimb): local layers use window-sized ring buffers."""
    gi = li = 0
    nk_g, nv_g = list(cache["k_global"]), list(cache["v_global"])
    nk_l, nv_l = list(cache["k_local"]), list(cache["v_local"])
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        if cfg.is_global_layer(i):
            x, nk, nv = _attn_decode_apply(
                lp, x, nk_g[gi], nv_g[gi], pos, cfg, eng, 0)
            nk_g[gi], nv_g[gi] = nk, nv
            gi += 1
        else:
            x, nk, nv = _attn_decode_apply_ring(
                lp, x, nk_l[li], nv_l[li], pos, cfg, eng, cfg.sliding_window)
            nk_l[li], nv_l[li] = nk, nv
            li += 1
        x = _mlp_apply(lp, x, cfg, eng)
    if isinstance(cache["k_global"], tuple):
        new_cache["k_global"], new_cache["v_global"] = tuple(nk_g), tuple(nv_g)
        new_cache["k_local"], new_cache["v_local"] = tuple(nk_l), tuple(nv_l)
    else:
        new_cache["k_global"] = jnp.stack(nk_g)
        new_cache["v_global"] = jnp.stack(nv_g)
        new_cache["k_local"] = jnp.stack(nk_l)
        new_cache["v_local"] = jnp.stack(nv_l)
    return x


# ---------------------------------------------------------------------------
# paged-KV serving: decode + chunked prefill against a page-table cache
# ---------------------------------------------------------------------------


def _scatter_targets(block_tables, positions, valid, page_size):
    """Physical (page, offset) scatter targets for logical ``positions``.

    ``positions`` may be (B,) (decode) or (B, C) (a prefill chunk); invalid
    writes (idle lanes, chunk padding) are routed to the null page 0, which
    no block table references.
    """
    nblk = block_tables.shape[1]
    blk = jnp.clip(positions // page_size, 0, nblk - 1)
    if positions.ndim == 1:                       # decode: (B,)
        rows = jnp.arange(block_tables.shape[0])
    else:                                         # prefill chunk: (B, C)
        rows = jnp.arange(block_tables.shape[0])[:, None]
    pidx = jnp.where(valid, block_tables[rows, blk], 0)
    poff = positions % page_size
    return pidx, poff


def _scatter_heads(pool, pidx, poff, val):
    """Write per-token values into one layer's head-major pool.

    ``pool``: ``(Hkv, P, page[, Dh])``; ``pidx`` / ``poff``: ``(B,)`` or
    ``(B, C)`` scatter targets; ``val``: ``(B[, C], Hkv[, Dh])`` — the
    head axis moves to the front to meet the pool's layout.
    """
    head_ax = pidx.ndim
    return pool.at[:, pidx, poff].set(
        jnp.moveaxis(val, head_ax, 0).astype(pool.dtype))


def decode_step_paged(
    params: Params,
    pages,                               # KVPages: k/v (L, Hkv, P, page, Dh)
    block_tables: jnp.ndarray,           # (B, n_blocks) int32
    pos: jnp.ndarray,                    # (B,) logical token count per lane
    active: jnp.ndarray,                 # (B,) bool — lanes decoding now
    tokens: jnp.ndarray,                 # (B, 1) or (B, 1, K) for audio
    cfg: ModelConfig,
    eng: Optional[EngineConfig] = None,
    attn_backend: Optional[str] = None,
    mesh=None,
    model_axis: str = "model",
) -> Tuple[jnp.ndarray, Any]:
    """One token of autoregressive decode over paged KV.

    Token-identical to :func:`decode_step` on the fixed-slot cache: the
    block table only relocates KV bytes into shared pages.  Inactive lanes
    (idle, or mid-prefill — their pages must stay frozen) scatter their
    garbage K/V into the null page and their logits are ignored by the
    caller.  ``attn_backend`` overrides the plan's resolved decode-read
    path (``gather`` reference vs the fused in-place Pallas kernel); None
    defers to the plan, and no plan means "auto".  ``mesh`` /
    ``model_axis`` shard_map the fused kernel over the pool's
    heads-over-model placement (None defers to the plan's mesh; the
    gather path uses its hints instead).  Returns
    ``(logits, new_pages)``.
    """
    eng = as_plan(eng)
    if attn_backend is None and eng is not None:
        attn_backend = eng.attn_backend
    attn_backend = resolve_attn_backend(attn_backend)
    if mesh is None and eng is not None:
        mesh, model_axis = eng.mesh, eng.model_axis
    b = tokens.shape[0]
    dh, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    if cfg.family == "audio":
        x = sum(
            jnp.take(params["embed"][k], tokens[..., k], axis=0)
            for k in range(cfg.n_codebooks)
        )
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    # lanes over the data axes (no-op off-mesh) — matches the pool's
    # pages-over-data placement so scatters stay local to the lane's shard
    x = shard_batch_seq(x)
    quant = pages.k_scale is not None
    pidx, poff = _scatter_targets(block_tables, pos, active,
                                  pages.page_size)
    windows = _layer_windows(cfg)
    pos2 = pos[:, None]

    def body(x, xs):
        lp, win = xs["lp"], xs["win"]
        kp, vp = xs["kp"], xs["vp"]
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = dense(lp["attn"]["wq"], h, eng).reshape(b, 1, hq, dh)
        k = dense(lp["attn"]["wk"], h, eng).reshape(b, 1, hkv, dh)
        v = dense(lp["attn"]["wv"], h, eng).reshape(b, 1, hkv, dh)
        q = apply_rope(q, pos2, cfg.rope_theta)
        k = apply_rope(k, pos2, cfg.rope_theta)
        ys = {}
        if quant:
            kq, ks_new = _quantize_kv(k[:, 0])
            vq, vs_new = _quantize_kv(v[:, 0])
            nkp = _scatter_heads(kp, pidx, poff, kq)
            nvp = _scatter_heads(vp, pidx, poff, vq)
            nks = _scatter_heads(xs["ks"], pidx, poff, ks_new)
            nvs = _scatter_heads(xs["vs"], pidx, poff, vs_new)
            o = attend_paged_decode(q, nkp, nvp, block_tables, pos, win,
                                    k_scale=nks, v_scale=nvs,
                                    attn_backend=attn_backend,
                                    mesh=mesh, model_axis=model_axis)
            ys["ks"], ys["vs"] = nks, nvs
        else:
            nkp = _scatter_heads(kp, pidx, poff, k[:, 0])
            nvp = _scatter_heads(vp, pidx, poff, v[:, 0])
            o = attend_paged_decode(q, nkp, nvp, block_tables, pos, win,
                                    attn_backend=attn_backend,
                                    mesh=mesh, model_axis=model_axis)
        o = dense(lp["attn"]["wo"], o.reshape(b, 1, hq * dh), eng)
        x = x + o
        if cfg.family == "moe":
            x, _ = _moe_apply(lp, x, cfg, eng)
        else:
            x = _mlp_apply(lp, x, cfg, eng)
        ys["kp"], ys["vp"] = nkp, nvp
        return x, ys

    xs = {"lp": params["layers"], "win": windows,
          "kp": pages.k, "vp": pages.v}
    if quant:
        xs["ks"], xs["vs"] = pages.k_scale, pages.v_scale
    x, ys = jax.lax.scan(body, x, xs)
    new_pages = pages.replace(
        k=ys["kp"], v=ys["vp"],
        k_scale=ys.get("ks"), v_scale=ys.get("vs"))
    logits = _lm_logits(params, x, cfg, eng)
    return logits, new_pages


def prefill_chunk(
    params: Params,
    pages,                               # KVPages
    block_tables: jnp.ndarray,           # (B, n_blocks) int32
    tokens: jnp.ndarray,                 # (B, C) or (B, C, K) for audio
    pos0: jnp.ndarray,                   # (B,) tokens already prefilled
    seq_lens: jnp.ndarray,               # (B,) total valid after this chunk
    cfg: ModelConfig,
    eng: Optional[EngineConfig] = None,
    attn_backend: Optional[str] = None,
    mesh=None,
    model_axis: str = "model",
) -> Tuple[jnp.ndarray, Any]:
    """One batched chunk of prompt prefill against paged KV.

    Lane ``b`` contributes tokens for logical positions
    ``[pos0[b], seq_lens[b])``; trailing chunk padding (and idle lanes,
    ``seq_lens == pos0``) is masked — padded K/V lands in the null page
    and padded queries attend nothing real.  Attention sees the lane's
    *full* resident prefix (pages written by earlier chunks or shared via
    the prefix cache) plus this chunk, so running ``prefill_chunk`` to
    completion over any chunk size matches the one-shot :func:`prefill`
    numerics.  ``attn_backend`` picks the read path like on the decode
    step: ``gather`` materializes the logical view per layer; the fused
    backends run the in-kernel prefill grid
    (:func:`repro.models.attention.attend_paged_prefill`) and the
    gathered ``(B, T, Hkv, Dh)`` view never exists.  Returns
    ``(last-valid-token logits (B, 1, V...), new_pages)``.
    """
    eng = as_plan(eng)
    if attn_backend is None and eng is not None:
        attn_backend = eng.attn_backend
    attn_backend = resolve_attn_backend(attn_backend)
    if mesh is None and eng is not None:
        mesh, model_axis = eng.mesh, eng.model_axis
    c = tokens.shape[1]
    dh, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    positions = pos0[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    valid_q = positions < seq_lens[:, None]
    x, positions = embed_inputs(
        params, {"tokens": tokens, "positions": positions}, cfg)
    x = shard_batch_seq(x)
    b = x.shape[0]
    quant = pages.k_scale is not None
    pidx, poff = _scatter_targets(block_tables, positions, valid_q,
                                  pages.page_size)
    windows = _layer_windows(cfg)

    def body(x, xs):
        lp, win = xs["lp"], xs["win"]
        kp, vp = xs["kp"], xs["vp"]
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = dense(lp["attn"]["wq"], h, eng).reshape(b, c, hq, dh)
        k = dense(lp["attn"]["wk"], h, eng).reshape(b, c, hkv, dh)
        v = dense(lp["attn"]["wv"], h, eng).reshape(b, c, hkv, dh)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        ys = {}
        if quant:
            kq, ks_new = _quantize_kv(k)
            vq, vs_new = _quantize_kv(v)
            nkp = _scatter_heads(kp, pidx, poff, kq)
            nvp = _scatter_heads(vp, pidx, poff, vq)
            nks = _scatter_heads(xs["ks"], pidx, poff, ks_new)
            nvs = _scatter_heads(xs["vs"], pidx, poff, vs_new)
            # scales stay folded into the probabilities on both read
            # paths (attend_dense_quant math == the fused grid's in-VMEM
            # folding) — the int8 view is never dequantized wholesale.
            o = attend_paged_prefill(q, nkp, nvp, block_tables, positions,
                                     pos0, seq_lens, win,
                                     k_scale=nks, v_scale=nvs,
                                     attn_backend=attn_backend,
                                     mesh=mesh, model_axis=model_axis)
            ys["ks"], ys["vs"] = nks, nvs
        else:
            nkp = _scatter_heads(kp, pidx, poff, k)
            nvp = _scatter_heads(vp, pidx, poff, v)
            o = attend_paged_prefill(q, nkp, nvp, block_tables, positions,
                                     pos0, seq_lens, win,
                                     attn_backend=attn_backend,
                                     mesh=mesh, model_axis=model_axis)
        o = dense(lp["attn"]["wo"], o.reshape(b, c, hq * dh), eng)
        x = x + o
        if cfg.family == "moe":
            x, _ = _moe_apply(lp, x, cfg, eng)
        else:
            x = _mlp_apply(lp, x, cfg, eng)
        ys["kp"], ys["vp"] = nkp, nvp
        return x, ys

    xs = {"lp": params["layers"], "win": windows,
          "kp": pages.k, "vp": pages.v}
    if quant:
        xs["ks"], xs["vs"] = pages.k_scale, pages.v_scale
    x, ys = jax.lax.scan(body, x, xs)
    new_pages = pages.replace(
        k=ys["kp"], v=ys["vp"],
        k_scale=ys.get("ks"), v_scale=ys.get("vs"))
    last = jnp.clip(seq_lens - pos0 - 1, 0, c - 1)
    h_last = x[jnp.arange(b), last][:, None]
    logits = _lm_logits(params, h_last, cfg, eng)
    return logits, new_pages


# ---------------------------------------------------------------------------
# engine quantization of trained params
# ---------------------------------------------------------------------------

_QUANT_KEYS = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
               "in_proj", "out_proj", "lm_head"}
# _ROW_PARALLEL (imported above from dist.sharding._ROW — one source of
# truth): these consume model-sharded activations, so the sharded backend
# must split their contraction axis to agree with the param placement.


def quantize_params(params: Params, cfg: ModelConfig, bits: int = 8) -> Params:
    """Convert trained params into IMAGine-engine serving format: every
    large linear becomes a :class:`~repro.engine.PackedLinear` (bit-packed
    along the contraction axis, ``bits`` validated and frozen into the
    pytree at pack time, mesh partition preference derived from the name).
    Embeddings, norms, convs, router stay dense."""

    def walk(node, name: str = ""):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k in _QUANT_KEYS:
                    part = "row" if k in _ROW_PARALLEL else "col"
                    if isinstance(v, dict) and "w" in v:  # {"w", "bias"?}
                        out[k] = pack_linear(v["w"], bits, bias=v.get("bias"),
                                             partition=part)
                    elif isinstance(v, jnp.ndarray) and v.ndim >= 2:
                        out[k] = pack_linear(v, bits)     # stacked experts
                    else:
                        out[k] = walk(v, k)
                else:
                    out[k] = walk(v, k)
            return out
        return node

    return walk(params)
