"""Decoder-only LM covering dense / MoE / SSM / hybrid / VLM families.

Layer stacking: layers are grouped into repeating *periods* (`cfg.scan_period()`;
1 for uniform stacks, 8 for Jamba's 1-attn:7-mamba pattern, 2 for every-other-
layer MoE).  Parameters for each position within the period are stacked over
the periods and the stack is driven by ``lax.scan`` (+ optional remat) — this
keeps the lowered HLO O(period) instead of O(n_layers), which matters both for
compile time and for the dry-run of 96-layer configs.

DeepSeek's "first layer dense-FFN" exception lives outside the scan
(``head_layers``).

Three entry points:
* :func:`lm_loss`      — next-token CE (+ MoE aux), the train-step objective.
* :func:`lm_prefill`   — logits + filled cache (inference-prefill shape).
* :func:`lm_decode`    — one token with cache (decode shapes).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import attention as A
from repro.models import mamba2 as M
from repro.models.config import ModelConfig
from repro.models.layers import KeyGen, init_rms_norm, normal_init, rms_norm, spec_rms_norm
from repro.models.mlp import init_mlp, mlp_forward, spec_mlp
from repro.models.moe import init_moe, moe_forward, spec_moe
from repro.models.rope import mrope_text_positions, rope_cos_sin, text_positions

PyTree = Any

# Named scopes of the LM's ends (``jax.named_scope``, metadata only): the
# embedding lookup, and the final norm with the vocabulary projection and
# the cross-entropy.  The blocks carry their own (``mamba2.*``).
SCOPE_EMBED = "lm.embed"
SCOPE_HEAD = "lm.head"


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Per-layer blocks
# ---------------------------------------------------------------------------


def init_block(kg: KeyGen, cfg: ModelConfig, kind: str, ffn_kind: str, dtype) -> Dict:
    p: Dict[str, Any] = {"norm1": init_rms_norm(cfg.d_model, dtype)}
    if kind == "attn":
        p["mixer"] = (
            A.init_mla(kg, cfg, dtype) if cfg.attn_impl == "mla" else A.init_gqa(kg, cfg, dtype)
        )
    elif kind == "mamba":
        p["mixer"] = M.init_mamba2(kg, cfg, dtype)
    else:
        raise ValueError(kind)
    if ffn_kind == "dense":
        p["norm2"] = init_rms_norm(cfg.d_model, dtype)
        p["ffn"] = init_mlp(kg, cfg.d_model, cfg.d_ff, cfg.mlp_type, cfg.init_scale, dtype)
    elif ffn_kind == "moe":
        p["norm2"] = init_rms_norm(cfg.d_model, dtype)
        p["ffn"] = init_moe(kg, cfg, dtype)
    return p


def spec_block(cfg: ModelConfig, kind: str, ffn_kind: str, model_axis="model") -> Dict:
    sp: Dict[str, Any] = {"norm1": spec_rms_norm()}
    if kind == "attn":
        sp["mixer"] = (
            A.spec_mla(cfg, model_axis) if cfg.attn_impl == "mla" else A.spec_gqa(cfg, model_axis)
        )
    else:
        sp["mixer"] = M.spec_mamba2(cfg, model_axis)
    if ffn_kind == "dense":
        sp["norm2"] = spec_rms_norm()
        sp["ffn"] = spec_mlp(cfg.mlp_type, model_axis)
    elif ffn_kind == "moe":
        sp["norm2"] = spec_rms_norm()
        sp["ffn"] = spec_moe(cfg, model_axis)
    return sp


def block_forward(
    params: Dict, cfg: ModelConfig, kind: str, ffn_kind: str, x, cos_sin
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    h = rms_norm(x, params["norm1"]["scale"], cfg.norm_eps)
    if kind == "attn":
        if cfg.attn_impl == "mla":
            h = A.mla_forward(params["mixer"], cfg, h, cos_sin)
        else:
            h = A.gqa_forward(params["mixer"], cfg, h, cos_sin)
    else:
        h = M.mamba2_forward(params["mixer"], cfg, h)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if ffn_kind != "none":
        h = rms_norm(x, params["norm2"]["scale"], cfg.norm_eps)
        if ffn_kind == "dense":
            h = mlp_forward(params["ffn"], cfg.mlp_type, h)
        else:
            h, aux = moe_forward(params["ffn"], cfg, h)
        x = x + h
    return x, aux


def block_decode(
    params: Dict, cfg: ModelConfig, kind: str, ffn_kind: str, x, cos_sin, cache, pos
) -> Tuple[jnp.ndarray, jnp.ndarray, Dict]:
    h = rms_norm(x, params["norm1"]["scale"], cfg.norm_eps)
    if kind == "attn":
        if cfg.attn_impl == "mla":
            h, cache = A.mla_decode(params["mixer"], cfg, h, cos_sin, cache, pos)
        else:
            h, cache = A.gqa_decode(params["mixer"], cfg, h, cos_sin, cache, pos)
    else:
        h, cache = M.mamba2_decode(params["mixer"], cfg, h, cache)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if ffn_kind != "none":
        h = rms_norm(x, params["norm2"]["scale"], cfg.norm_eps)
        if ffn_kind == "dense":
            h = mlp_forward(params["ffn"], cfg.mlp_type, h)
        else:
            h, aux = moe_forward(params["ffn"], cfg, h)
        x = x + h
    return x, aux, cache


# ---------------------------------------------------------------------------
# Whole-model parameters
# ---------------------------------------------------------------------------


def _period_patterns(cfg: ModelConfig):
    """(head_patterns, period_pattern, n_periods): lists of (kind, ffn_kind)."""
    kinds = cfg.layer_kinds()
    ffns = cfg.ffn_kinds()
    pairs = list(zip(kinds, ffns))
    head = pairs[: cfg.first_k_dense]
    body = pairs[cfg.first_k_dense :]
    period = cfg.scan_period()
    assert len(body) % period == 0
    return head, body[:period], len(body) // period


def init_lm(key, cfg: ModelConfig) -> PyTree:
    dtype = _dtype(cfg)
    kg = KeyGen(key)
    head_pat, period_pat, n_periods = _period_patterns(cfg)
    params: Dict[str, Any] = {
        "embed": normal_init(kg(), (cfg.vocab_size, cfg.d_model), cfg.init_scale, dtype),
        "final_norm": init_rms_norm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal_init(
            kg(), (cfg.d_model, cfg.vocab_size), cfg.init_scale, dtype
        )
    params["head_layers"] = [
        init_block(kg, cfg, k, f, dtype) for (k, f) in head_pat
    ]
    layers = {}
    for i, (k, f) in enumerate(period_pat):
        stacked = [init_block(kg, cfg, k, f, dtype) for _ in range(n_periods)]
        layers[f"pos{i}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)
    params["layers"] = layers
    return params


def lm_param_specs(cfg: ModelConfig, model_axis: str = "model") -> PyTree:
    head_pat, period_pat, n_periods = _period_patterns(cfg)
    specs: Dict[str, Any] = {
        "embed": P(model_axis, None),
        "final_norm": spec_rms_norm(),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, model_axis)
    specs["head_layers"] = [spec_block(cfg, k, f, model_axis) for (k, f) in head_pat]
    layers = {}
    for i, (k, f) in enumerate(period_pat):
        sp = spec_block(cfg, k, f, model_axis)
        # account for the stacked leading period axis
        layers[f"pos{i}"] = jax.tree.map(
            lambda s: P(None, *s), sp, is_leaf=lambda s: isinstance(s, P)
        )
    specs["layers"] = layers
    return specs


# ---------------------------------------------------------------------------
# Position tables
# ---------------------------------------------------------------------------


def _cos_sin(cfg: ModelConfig, positions, batch, seq, offset=0):
    if cfg.arch_type == "ssm" or not _uses_rope(cfg):
        return None
    hd = cfg.resolved_head_dim if cfg.attn_impl != "mla" else cfg.mla.rope_head_dim
    if positions is None:
        if cfg.mrope_sections is not None:
            positions = mrope_text_positions(batch, seq, offset)
        else:
            positions = text_positions(batch, seq, offset)
    return rope_cos_sin(positions, hd, cfg.rope_theta, cfg.mrope_sections)


def _uses_rope(cfg: ModelConfig) -> bool:
    # Jamba uses no positional encoding (Mamba layers carry position).
    return cfg.arch_type != "hybrid"


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _embed(params, cfg: ModelConfig, tokens, prefix_embeds):
    x = params["embed"][tokens]  # (B, S_txt, d)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    return x


def lm_forward(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # (B, S_txt)
    *,
    prefix_embeds: Optional[jnp.ndarray] = None,  # (B, S_img, d) VLM/audio stub
    positions: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full causal forward; returns (logits (B,S,V), moe_aux)."""
    with jax.named_scope(SCOPE_EMBED):
        x = _embed(params, cfg, tokens, prefix_embeds)
    b, s, _ = x.shape
    cos_sin = _cos_sin(cfg, positions, b, s)
    head_pat, period_pat, _ = _period_patterns(cfg)

    aux = jnp.zeros((), jnp.float32)
    for bp, (k, f) in zip(params["head_layers"], head_pat):
        x, a = block_forward(bp, cfg, k, f, x, cos_sin)
        aux = aux + a

    def period_body(x_in, period_params):
        a_tot = jnp.zeros((), jnp.float32)
        xx = x_in
        for i, (k, f) in enumerate(period_pat):
            xx, a = block_forward(period_params[f"pos{i}"], cfg, k, f, xx, cos_sin)
            a_tot = a_tot + a
        return xx, a_tot

    body = period_body
    if cfg.remat:
        body = _remat(cfg, period_body)
    x, auxs = jax.lax.scan(body, x, params["layers"], unroll=cfg.scan_unroll or 1)
    aux = aux + jnp.sum(auxs)

    with jax.named_scope(SCOPE_HEAD):
        x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = x @ head
    return logits, aux


def _remat(cfg: ModelConfig, fn):
    """Rematerialization with the configured policy (§Perf lever)."""
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


def _hidden_states(params: PyTree, cfg: ModelConfig, tokens, prefix_embeds, positions):
    """Forward to the final norm WITHOUT projecting to the vocabulary."""
    with jax.named_scope(SCOPE_EMBED):
        x = _embed(params, cfg, tokens, prefix_embeds)
    b, s, _ = x.shape
    cos_sin = _cos_sin(cfg, positions, b, s)
    head_pat, period_pat, _ = _period_patterns(cfg)
    aux = jnp.zeros((), jnp.float32)
    for bp, (k, f) in zip(params["head_layers"], head_pat):
        x, a = block_forward(bp, cfg, k, f, x, cos_sin)
        aux = aux + a

    def period_body(x_in, period_params):
        a_tot = jnp.zeros((), jnp.float32)
        xx = x_in
        for i, (k, f) in enumerate(period_pat):
            xx, a = block_forward(period_params[f"pos{i}"], cfg, k, f, xx, cos_sin)
            a_tot = a_tot + a
        return xx, a_tot

    body = _remat(cfg, period_body) if cfg.remat else period_body
    x, auxs = jax.lax.scan(body, x, params["layers"], unroll=cfg.scan_unroll or 1)
    aux = aux + jnp.sum(auxs)
    with jax.named_scope(SCOPE_HEAD):
        return rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps), aux


def _chunked_ce(hidden: jnp.ndarray, head: jnp.ndarray, targets: jnp.ndarray, chunk: int):
    """Next-token CE via a scan over sequence chunks: the (chunk, V) logits
    block is the only vocabulary-sized tensor ever live (§Perf: removes the
    full (B, S, V) materialization from both HBM traffic and peak memory)."""
    b, s_pred, d = hidden.shape
    chunk = min(chunk, s_pred)
    n_full = s_pred // chunk
    rem = s_pred - n_full * chunk

    def ce_of(h_blk, t_blk):
        logits = (h_blk @ head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t_blk[..., None], axis=-1)[..., 0]
        return jnp.sum(logz - gold)

    total = jnp.zeros((), jnp.float32)
    if n_full:
        h_main = hidden[:, : n_full * chunk].reshape(b, n_full, chunk, d)
        t_main = targets[:, : n_full * chunk].reshape(b, n_full, chunk)

        def body(acc, blk):
            h_blk, t_blk = blk
            return acc + ce_of(h_blk, t_blk), None

        total, _ = jax.lax.scan(
            body, total, (jnp.moveaxis(h_main, 1, 0), jnp.moveaxis(t_main, 1, 0))
        )
    if rem:
        total = total + ce_of(hidden[:, n_full * chunk :], targets[:, n_full * chunk :])
    return total / (b * s_pred)


def lm_loss(params: PyTree, cfg: ModelConfig, batch: Dict) -> jnp.ndarray:
    """Next-token cross-entropy over the text tokens (+ MoE aux loss).

    batch: {"tokens": (B, S)} (+ "prefix_embeds", "positions" for vlm/audio).
    """
    tokens = batch["tokens"]
    if cfg.loss_chunk > 0:
        hidden, aux = _hidden_states(
            params, cfg, tokens,
            batch.get("prefix_embeds"), batch.get("positions"),
        )
        with jax.named_scope(SCOPE_HEAD):
            head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
            txt_hidden = hidden[:, -tokens.shape[1] : -1, :]
            ce = _chunked_ce(txt_hidden, head, tokens[:, 1:], cfg.loss_chunk)
        return ce + aux
    logits, aux = lm_forward(
        params,
        cfg,
        tokens,
        prefix_embeds=batch.get("prefix_embeds"),
        positions=batch.get("positions"),
    )
    # align: predict token t+1 from position t (text-only tail of the stream)
    with jax.named_scope(SCOPE_HEAD):
        txt_logits = logits[:, -tokens.shape[1] :, :]
        pred = txt_logits[:, :-1].astype(jnp.float32)
        tgt = tokens[:, 1:]
        logz = jax.nn.logsumexp(pred, axis=-1)
        gold = jnp.take_along_axis(pred, tgt[..., None], axis=-1)[..., 0]
        ce = jnp.mean(logz - gold)
    return ce + aux


# ---------------------------------------------------------------------------
# KV / SSM caches
# ---------------------------------------------------------------------------


def _init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_seq: int, dtype):
    if kind == "attn":
        if cfg.attn_impl == "mla":
            return A.init_mla_cache(cfg, batch, max_seq, dtype)
        return A.init_gqa_cache(cfg, batch, max_seq, dtype)
    return M.init_mamba2_cache(cfg, batch, dtype)


def _spec_block_cache(cfg: ModelConfig, kind: str, batch_axes, model_axis):
    if kind == "attn":
        if cfg.attn_impl == "mla":
            return A.spec_mla_cache(cfg, batch_axes, model_axis)
        return A.spec_gqa_cache(cfg, batch_axes, model_axis)
    return M.spec_mamba2_cache(cfg, batch_axes, model_axis)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> Dict:
    dtype = _dtype(cfg)
    head_pat, period_pat, n_periods = _period_patterns(cfg)
    cache: Dict[str, Any] = {
        "pos": jnp.zeros((), jnp.int32),
        "head_layers": [
            _init_block_cache(cfg, k, batch, max_seq, dtype) for (k, _) in head_pat
        ],
    }
    layers = {}
    for i, (k, _) in enumerate(period_pat):
        one = _init_block_cache(cfg, k, batch, max_seq, dtype)
        layers[f"pos{i}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_periods,) + x.shape), one
        )
    cache["layers"] = layers
    return cache


def cache_specs(cfg: ModelConfig, batch_axes, model_axis: str = "model") -> Dict:
    head_pat, period_pat, _ = _period_patterns(cfg)
    specs: Dict[str, Any] = {
        "pos": P(),
        "head_layers": [
            _spec_block_cache(cfg, k, batch_axes, model_axis) for (k, _) in head_pat
        ],
    }
    layers = {}
    for i, (k, _) in enumerate(period_pat):
        sp = _spec_block_cache(cfg, k, batch_axes, model_axis)
        layers[f"pos{i}"] = jax.tree.map(
            lambda s: P(None, *s), sp, is_leaf=lambda s: isinstance(s, P)
        )
    specs["layers"] = layers
    return specs


def lm_decode(
    params: PyTree,
    cfg: ModelConfig,
    token: jnp.ndarray,  # (B, 1) int32
    cache: Dict,
) -> Tuple[jnp.ndarray, Dict]:
    """One decode step; returns (logits (B,1,V), updated cache)."""
    pos = cache["pos"]
    x = params["embed"][token]
    b = x.shape[0]
    head_pat, period_pat, _ = _period_patterns(cfg)
    if _uses_rope(cfg) and cfg.arch_type != "ssm":
        posn = (
            mrope_text_positions(b, 1, pos)
            if cfg.mrope_sections is not None
            else text_positions(b, 1, pos)
        )
        hd = cfg.resolved_head_dim if cfg.attn_impl != "mla" else cfg.mla.rope_head_dim
        cos_sin = rope_cos_sin(posn, hd, cfg.rope_theta, cfg.mrope_sections)
    else:
        cos_sin = None

    new_head_caches = []
    for bp, (k, f), cc in zip(params["head_layers"], head_pat, cache["head_layers"]):
        x, _, cc = block_decode(bp, cfg, k, f, x, cos_sin, cc, pos)
        new_head_caches.append(cc)

    def period_body(x_in, scanned):
        period_params, period_cache = scanned
        xx = x_in
        new_cc = {}
        for i, (k, f) in enumerate(period_pat):
            xx, _, cc = block_decode(
                period_params[f"pos{i}"], cfg, k, f, xx, cos_sin, period_cache[f"pos{i}"], pos
            )
            new_cc[f"pos{i}"] = cc
        return xx, new_cc

    x, new_layer_caches = jax.lax.scan(period_body, x, (params["layers"], cache["layers"]), unroll=cfg.scan_unroll or 1)

    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    new_cache = {
        "pos": pos + 1,
        "head_layers": new_head_caches,
        "layers": new_layer_caches,
    }
    return logits, new_cache


def lm_prefill(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    cache: Dict,
    *,
    prefix_embeds: Optional[jnp.ndarray] = None,
    positions: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict]:
    """Prefill = full causal forward + cache fill.

    For attention layers the K/V computed during the forward are re-derived
    per layer and written into the cache; for mamba layers the final SSM/conv
    states are produced by the same chunked scan."""
    x = _embed(params, cfg, tokens, prefix_embeds)
    b, s, _ = x.shape
    cos_sin = _cos_sin(cfg, positions, b, s)
    head_pat, period_pat, _ = _period_patterns(cfg)

    def prefill_block(bp, kind, ffn_kind, xx, cc):
        h = rms_norm(xx, bp["norm1"]["scale"], cfg.norm_eps)
        if kind == "attn":
            if cfg.attn_impl == "mla":
                q_nope, q_rope, c_kv, k_rope = A._mla_qkr(bp["mixer"], cfg, h, cos_sin)
                cc = A.mla_fill_cache(cc, c_kv, k_rope)
                out = A.mla_forward(bp["mixer"], cfg, h, cos_sin)
            else:
                q, k, v = A._project_qkv(bp["mixer"], cfg, h)
                if cos_sin is not None:
                    q = A.apply_rope(q, *cos_sin)
                    k = A.apply_rope(k, *cos_sin)
                cc = A.gqa_fill_cache(cc, k, v)
                core = A.attention_core(
                    q, k, v, causal=True, window=cfg.sliding_window,
                    chunk=cfg.attn_chunk, softcap=cfg.attn_logit_softcap,
                )
                out = jnp.einsum("bshk,hkd->bsd", core, bp["mixer"]["wo"])
        else:
            s_cfg, d_in, n_heads, _ = M._dims(cfg)
            zxbcdt = h @ bp["mixer"]["in_proj"]
            z, xbc, dt_raw = M._split_proj(cfg, zxbcdt)
            conv_full = M.causal_conv(xbc, bp["mixer"]["conv_w"], bp["mixer"]["conv_b"])
            conv_win = xbc[:, -(s_cfg.d_conv - 1) :, :]
            xbc_act = jax.nn.silu(conv_full)
            xm, b_mat, c_mat = M._split_xbc(cfg, xbc_act)
            dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + bp["mixer"]["dt_bias"])
            a_neg = -jnp.exp(bp["mixer"]["a_log"])
            y, final_state = M.ssd_reference(
                xm, dt.astype(xm.dtype), a_neg, b_mat, c_mat, chunk=s_cfg.chunk
            )
            y = y.astype(xx.dtype) + bp["mixer"]["d_skip"].astype(xx.dtype)[None, None, :, None] * xm
            y = y.reshape(xx.shape[0], xx.shape[1], d_in)
            y = rms_norm(y * jax.nn.silu(z), bp["mixer"]["norm"], cfg.norm_eps)
            out = y @ bp["mixer"]["out_proj"]
            cc = {"conv": conv_win, "ssm": final_state.astype(jnp.float32)}
        xx = xx + out
        if ffn_kind != "none":
            h2 = rms_norm(xx, bp["norm2"]["scale"], cfg.norm_eps)
            if ffn_kind == "dense":
                h2 = mlp_forward(bp["ffn"], cfg.mlp_type, h2)
            else:
                h2, _ = moe_forward(bp["ffn"], cfg, h2)
            xx = xx + h2
        return xx, cc

    new_head_caches = []
    for bp, (k, f), cc in zip(params["head_layers"], head_pat, cache["head_layers"]):
        x, cc = prefill_block(bp, k, f, x, cc)
        new_head_caches.append(cc)

    def period_body(x_in, scanned):
        pp, pc = scanned
        xx = x_in
        new_cc = {}
        for i, (k, f) in enumerate(period_pat):
            xx, cc = prefill_block(pp[f"pos{i}"], k, f, xx, pc[f"pos{i}"])
            new_cc[f"pos{i}"] = cc
        return xx, new_cc

    x, new_layer_caches = jax.lax.scan(period_body, x, (params["layers"], cache["layers"]), unroll=cfg.scan_unroll or 1)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    new_cache = {
        "pos": jnp.asarray(x.shape[1], jnp.int32),
        "head_layers": new_head_caches,
        "layers": new_layer_caches,
    }
    return logits, new_cache
