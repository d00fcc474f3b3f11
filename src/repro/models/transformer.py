"""Decoder-only LM assembly for dense / MoE / SSM / hybrid / VLM families.

Structure decisions that matter at scale:

* **scan-over-layers** — homogeneous layers are stacked on a leading
  ``layers`` axis and driven by ``lax.scan``; HLO size is O(1) in depth, so
  the 126-layer llama3-405b compiles in seconds on the dry-run host.  The
  hybrid family scans over period-groups of its block pattern and unrolls
  the remainder.
* **remat as a PP** — ``cfg.remat ∈ {none, full, dots}`` wraps the scan body
  in ``jax.checkpoint``; the tuner can trade the memory term against the
  compute term and the HLO-FLOPs ratio in §Roofline makes the recompute
  visible.
* Three entry points per family: full-sequence ``forward`` (training),
  ``prefill`` (returns a KV/state cache), ``decode_step`` (one token).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import constrain

from .attention import (
    attn_spec,
    blocked_causal_attention,
    decode_attention,
    flash_attention_xla,
    full_attention,
    local_window_attention,
    output_proj,
    project_qkv,
)
from .config import ModelConfig
from .layers import (
    embed,
    embed_spec,
    gelu_mlp,
    gelu_mlp_spec,
    rmsnorm,
    rmsnorm_spec,
    swiglu,
    swiglu_spec,
    unembed,
    unembed_spec,
)
from .moe import moe_block, moe_spec
from .rglru import rglru_block, rglru_decode_step, rglru_init_cache, rglru_spec
from .spec import ParamSpec
from .ssm import ssm_block, ssm_decode_step, ssm_init_cache, ssm_spec


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def decoder_specs(cfg: ModelConfig) -> Dict[str, Any]:
    L = cfg.n_layers
    specs: Dict[str, Any] = {
        "embed": embed_spec(cfg),
        "final_norm": rmsnorm_spec(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = unembed_spec(cfg)

    if cfg.family in ("dense", "vlm"):
        specs["layers"] = {
            "ln1": ParamSpec((L, cfg.d_model), ("layers", "embed"), init="ones"),
            "attn": attn_spec(cfg, layers=L),
            "ln2": ParamSpec((L, cfg.d_model), ("layers", "embed"), init="ones"),
            "mlp": swiglu_spec(cfg.d_model, cfg.d_ff, layers=L),
        }
    elif cfg.family == "moe":
        specs["layers"] = {
            "ln1": ParamSpec((L, cfg.d_model), ("layers", "embed"), init="ones"),
            "attn": attn_spec(cfg, layers=L),
            "ln2": ParamSpec((L, cfg.d_model), ("layers", "embed"), init="ones"),
            "moe": moe_spec(cfg, layers=L),
        }
    elif cfg.family == "ssm":
        specs["layers"] = {
            "ln": ParamSpec((L, cfg.d_model), ("layers", "embed"), init="ones"),
            "ssm": ssm_spec(cfg, layers=L),
        }
    elif cfg.family == "hybrid":
        period = len(cfg.block_pattern)
        n_groups, n_tail = divmod(L, period)
        group: Dict[str, Any] = {}
        for idx, kind in enumerate(cfg.block_pattern):
            group[f"b{idx}_{kind}"] = _hybrid_block_spec(cfg, kind, layers=n_groups)
        specs["groups"] = group
        if n_tail:
            tail_kinds = cfg.block_pattern[:n_tail]
            if len(set(tail_kinds)) == 1:  # homogeneous tail -> small scan
                specs["tail"] = {
                    f"t_{tail_kinds[0]}": _hybrid_block_spec(
                        cfg, tail_kinds[0], layers=n_tail
                    )
                }
            else:  # unroll
                specs["tail"] = {
                    f"t{idx}_{kind}": _hybrid_block_spec(cfg, kind, layers=None)
                    for idx, kind in enumerate(tail_kinds)
                }
    else:
        raise ValueError(f"decoder_specs: unsupported family {cfg.family}")
    return specs


def _hybrid_block_spec(
    cfg: ModelConfig, kind: str, layers: Optional[int]
) -> Dict[str, Any]:
    L = (layers,) if layers else ()
    la = ("layers",) if layers else ()
    base = {
        "ln1": ParamSpec(L + (cfg.d_model,), la + ("embed",), init="ones"),
        "ln2": ParamSpec(L + (cfg.d_model,), la + ("embed",), init="ones"),
        "mlp": swiglu_spec(cfg.d_model, cfg.d_ff, layers=layers),
    }
    if kind == "rec":
        base["rec"] = rglru_spec(cfg, layers=layers)
    elif kind == "attn":
        base["attn"] = attn_spec(cfg, layers=layers)
    else:
        raise ValueError(f"unknown hybrid block kind {kind!r}")
    return base


# ---------------------------------------------------------------------------
# Layer applications (single layer, unstacked params)
# ---------------------------------------------------------------------------


def _maybe_checkpoint(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    raise ValueError(f"unknown remat policy {cfg.remat!r}")


def _attention_mix(
    x: jnp.ndarray,
    p: Dict[str, Any],
    cfg: ModelConfig,
    positions: Optional[jnp.ndarray],
    window: Optional[int] = None,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Pre-norm attention with residual.  Returns (x, (k, v)) for caching."""
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    q, k, v = project_qkv(h, p["attn"], cfg, positions)
    S = x.shape[1]
    if window is not None:
        if S % min(cfg.attn_block_q, S) == 0 and S > window:
            o = local_window_attention(q, k, v, window, cfg.attn_block_q)
        else:
            o = full_attention(q, k, v, causal=True)  # small-seq fallback
    elif S > 2048 and S % min(cfg.attn_block_q, S) == 0 and S % min(
        cfg.attn_block_kv, S
    ) == 0:
        o = flash_attention_xla(q, k, v, cfg.attn_block_q, cfg.attn_block_kv)
    else:
        o = full_attention(q, k, v, causal=True)
    x = x + output_proj(o, p["attn"])
    return x, (k, v)


def _dense_layer(x, p, cfg: ModelConfig, positions):
    x, kv = _attention_mix(x, p, cfg, positions)
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    x = x + swiglu(h, p["mlp"])
    x = constrain(x, ("batch", "seq", "act_embed"))
    return x, kv, jnp.float32(0.0)


def _moe_layer(x, p, cfg: ModelConfig, positions):
    x, kv = _attention_mix(x, p, cfg, positions)
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    delta, aux = moe_block(h, p["moe"], cfg)
    x = x + delta
    x = constrain(x, ("batch", "seq", "act_embed"))
    return x, kv, aux


def _ssm_layer(x, p, cfg: ModelConfig, positions):
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    x = x + ssm_block(h, p["ssm"], cfg)
    x = constrain(x, ("batch", "seq", "act_embed"))
    return x, None, jnp.float32(0.0)


def _hybrid_layer(x, p, cfg: ModelConfig, positions, kind: str):
    if kind == "rec":
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        x = x + rglru_block(h, p["rec"], cfg)
        kv = None
    else:
        x, kv = _attention_mix(x, p, cfg, positions, window=cfg.local_window)
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    x = x + swiglu(h, p["mlp"])
    x = constrain(x, ("batch", "seq", "act_embed"))
    return x, kv, jnp.float32(0.0)


# ---------------------------------------------------------------------------
# Full-sequence forward (training) — logits over all positions
# ---------------------------------------------------------------------------


def forward(
    params: Dict[str, Any],
    tokens: jnp.ndarray,  # (B, S) int32
    cfg: ModelConfig,
    positions: Optional[jnp.ndarray] = None,
    vision_embeds: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits (B,S,V) fp32, aux_loss scalar)."""
    x, positions = _embed_inputs(params, tokens, cfg, positions, vision_embeds)
    x, aux = _apply_trunk(params, x, cfg, positions)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x, cfg)
    return logits, aux


def _embed_inputs(params, tokens, cfg, positions, vision_embeds):
    x = embed(tokens, params["embed"])
    if cfg.family == "vlm" and vision_embeds is not None:
        V = vision_embeds.shape[1]
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x[:, V:]], axis=1)
    if positions is None:
        B, S = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        positions = jnp.broadcast_to(pos, (3, B, S)) if cfg.mrope else pos
    x = constrain(x, ("batch", "seq", "act_embed"))
    return x, positions


def _logits(params, x, cfg):
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = unembed(x, w)
    return constrain(logits, ("batch", "seq", "act_vocab"))


def _apply_trunk(params, x, cfg: ModelConfig, positions):
    """Scan the layer stack in full-sequence mode."""
    layer_fn = {
        "dense": _dense_layer,
        "vlm": _dense_layer,
        "moe": _moe_layer,
        "ssm": _ssm_layer,
    }.get(cfg.family)

    if layer_fn is not None:
        def body(carry, lp):
            h, aux = carry
            h, _, a = layer_fn(h, lp, cfg, positions)
            return (h, aux + a), None

        body = _maybe_checkpoint(body, cfg)
        if cfg.scan_layers:
            (x, aux), _ = lax.scan(body, (x, jnp.float32(0.0)), params["layers"])
        else:
            aux = jnp.float32(0.0)
            L = cfg.n_layers
            for i in range(L):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                (x, aux), _ = body((x, aux), lp)
        return x, aux

    if cfg.family == "hybrid":
        pattern = cfg.block_pattern

        def group_body(carry, gp):
            h, aux = carry
            for idx, kind in enumerate(pattern):
                h, _, a = _hybrid_layer(h, gp[f"b{idx}_{kind}"], cfg, positions, kind)
                aux = aux + a
            return (h, aux), None

        group_body = _maybe_checkpoint(group_body, cfg)
        (x, aux), _ = lax.scan(group_body, (x, jnp.float32(0.0)), params["groups"])
        x, aux = _apply_hybrid_tail(params, x, aux, cfg, positions)
        return x, aux

    raise ValueError(f"forward: unsupported family {cfg.family}")


def _apply_hybrid_tail(params, x, aux, cfg, positions):
    if "tail" not in params:
        return x, aux
    for key, tp in params["tail"].items():
        kind = key.split("_", 1)[1]
        if key.startswith("t_"):  # stacked homogeneous tail
            def tail_body(carry, lp, _kind=kind):
                h, a0 = carry
                h, _, a = _hybrid_layer(h, lp, cfg, positions, _kind)
                return (h, a0 + a), None

            (x, aux), _ = lax.scan(
                _maybe_checkpoint(tail_body, cfg), (x, aux), tp
            )
        else:  # unrolled single layer
            x, _, a = _hybrid_layer(x, tp, cfg, positions, kind)
            aux = aux + a
    return x, aux


# ---------------------------------------------------------------------------
# Prefill — full-sequence forward that also builds the decode cache
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, capacity: int) -> Dict[str, Any]:
    """Zeroed decode cache.  ``capacity`` counts KV slots for attention
    families (ring-buffer of ``local_window`` for hybrid attention blocks);
    SSM/RG-LRU states are O(1)."""
    L = cfg.n_layers
    kv, hd = cfg.n_kv_heads, cfg.head_dim_
    if cfg.family in ("dense", "vlm", "moe"):
        return {
            "k": jnp.zeros((L, batch, capacity, kv, hd), jnp.bfloat16),
            "v": jnp.zeros((L, batch, capacity, kv, hd), jnp.bfloat16),
            "len": jnp.zeros((), jnp.int32),
        }
    if cfg.family == "ssm":
        base = ssm_init_cache(cfg, batch)
        return {
            "conv": jnp.zeros((L,) + base["conv"].shape, base["conv"].dtype),
            "h": jnp.zeros((L,) + base["h"].shape, base["h"].dtype),
            "len": jnp.zeros((), jnp.int32),
        }
    if cfg.family == "hybrid":
        period = len(cfg.block_pattern)
        n_groups, n_tail = divmod(L, period)
        W = min(cfg.local_window, capacity)
        rec = rglru_init_cache(cfg, batch)
        cache: Dict[str, Any] = {"len": jnp.zeros((), jnp.int32)}
        for idx, kind in enumerate(cfg.block_pattern):
            if kind == "rec":
                cache[f"b{idx}_conv"] = jnp.zeros(
                    (n_groups,) + rec["conv"].shape, rec["conv"].dtype
                )
                cache[f"b{idx}_h"] = jnp.zeros(
                    (n_groups,) + rec["h"].shape, rec["h"].dtype
                )
            else:
                cache[f"b{idx}_k"] = jnp.zeros(
                    (n_groups, batch, W, kv, hd), jnp.bfloat16
                )
                cache[f"b{idx}_v"] = jnp.zeros(
                    (n_groups, batch, W, kv, hd), jnp.bfloat16
                )
        for t in range(n_tail):
            kind = cfg.block_pattern[t]
            if kind == "rec":
                cache[f"t{t}_conv"] = jnp.zeros_like(rec["conv"])
                cache[f"t{t}_h"] = jnp.zeros_like(rec["h"])
            else:
                cache[f"t{t}_k"] = jnp.zeros((batch, W, kv, hd), jnp.bfloat16)
                cache[f"t{t}_v"] = jnp.zeros((batch, W, kv, hd), jnp.bfloat16)
        return cache
    raise ValueError(f"init_cache: unsupported family {cfg.family}")


def prefill(
    params: Dict[str, Any],
    tokens: jnp.ndarray,
    cfg: ModelConfig,
    positions: Optional[jnp.ndarray] = None,
    vision_embeds: Optional[jnp.ndarray] = None,
    capacity: Optional[int] = None,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Returns (last-token logits (B, V), populated cache with len=S)."""
    B, S = tokens.shape
    cap = capacity or S
    x, positions = _embed_inputs(params, tokens, cfg, positions, vision_embeds)

    if cfg.family in ("dense", "vlm", "moe"):
        layer_fn = _moe_layer if cfg.family == "moe" else _dense_layer

        def body(carry, lp):
            h, aux = carry
            h, (k, v), a = layer_fn(h, lp, cfg, positions)
            return (h, aux + a), (_pad_cap(k, cap), _pad_cap(v, cap))

        (x, _), (ks, vs) = lax.scan(body, (x, jnp.float32(0.0)), params["layers"])
        cache = {
            "k": ks.astype(jnp.bfloat16),
            "v": vs.astype(jnp.bfloat16),
            "len": jnp.asarray(S, jnp.int32),
        }
    elif cfg.family == "ssm":
        # Run the full-sequence path for logits, then rebuild final state by
        # replaying the last d_conv window + final h via a stateful pass.
        # Cheap honest alternative: scan returning final (conv, h) per layer.
        def body(carry, lp):
            h_x, _ = carry
            hh = rmsnorm(h_x, lp["ln"], cfg.norm_eps)
            y, final = _ssm_block_with_state(hh, lp["ssm"], cfg)
            return (h_x + y, jnp.float32(0.0)), final

        (x, _), finals = lax.scan(body, (x, jnp.float32(0.0)), params["layers"])
        cache = {
            "conv": finals["conv"],
            "h": finals["h"],
            "len": jnp.asarray(S, jnp.int32),
        }
    elif cfg.family == "hybrid":
        cache = init_cache(cfg, B, cap)
        x, cache = _hybrid_prefill(params, x, cfg, positions, cache, S)
        cache["len"] = jnp.asarray(S, jnp.int32)
    else:
        raise ValueError(cfg.family)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x[:, -1:, :], cfg)[:, 0]
    return logits, cache


def _conv_tail(xs_raw: jnp.ndarray, K: int) -> jnp.ndarray:
    """Last K-1 pre-conv inputs as the decode conv state, zero-left-padded
    when the prompt is shorter than K-1 (the causal conv's implicit zeros);
    without the pad a short prefill hands decode a truncated window."""
    tail = xs_raw[:, max(0, xs_raw.shape[1] - (K - 1)):, :]
    short = (K - 1) - tail.shape[1]
    if short > 0:
        tail = jnp.pad(tail, ((0, 0), (short, 0), (0, 0)))
    return tail.astype(jnp.bfloat16)


def _pad_cap(k: jnp.ndarray, cap: int) -> jnp.ndarray:
    S = k.shape[1]
    if S == cap:
        return k
    if S > cap:
        return k[:, S - cap :]
    return jnp.pad(k, ((0, 0), (0, cap - S), (0, 0), (0, 0)))


def _ssm_block_with_state(x, p, cfg):
    """ssm_block that also returns the final (conv window, h) state."""
    from .ssm import _causal_conv1d

    B, S, _ = x.shape
    di, N, R, K = cfg.d_inner, cfg.ssm_state, cfg.dt_rank_, cfg.d_conv
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xs_raw, z = jnp.split(xz, 2, axis=-1)
    xs = jax.nn.silu(_causal_conv1d(xs_raw, p["conv_w"], p["conv_b"]))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    raw_all = jnp.einsum("bsd,dr->bsr", xs, p["x_proj"])

    def step(h, inputs):
        x_t, raw = inputs
        dt = jax.nn.softplus(
            jnp.einsum("br,rd->bd", raw[:, :R], p["dt_w"]).astype(jnp.float32)
            + p["dt_b"].astype(jnp.float32)
        )
        B_t = raw[:, R : R + N].astype(jnp.float32)
        C_t = raw[:, R + N :].astype(jnp.float32)
        decay = jnp.exp(dt[..., None] * A)
        h = decay * h + (dt * x_t.astype(jnp.float32))[..., None] * B_t[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y.astype(x.dtype)

    h0 = jnp.zeros((B, di, N), jnp.float32)
    h_final, ys = lax.scan(
        step, h0, (xs.transpose(1, 0, 2), raw_all.transpose(1, 0, 2))
    )
    y = ys.transpose(1, 0, 2) + xs * p["D"]
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bsd,de->bse", y, p["out_proj"])
    conv_state = _conv_tail(xs_raw, K)
    return out, {"conv": conv_state, "h": h_final}


def _hybrid_prefill(params, x, cfg, positions, cache, S):
    period = len(cfg.block_pattern)
    W = cache[f"b{_first_attn_idx(cfg)}_k"].shape[2] if _first_attn_idx(cfg) is not None else cfg.local_window

    def group_body(carry, gp):
        h = carry
        outs = {}
        for idx, kind in enumerate(cfg.block_pattern):
            lp = gp[f"b{idx}_{kind}"]
            if kind == "rec":
                hh = rmsnorm(h, lp["ln1"], cfg.norm_eps)
                y, final = _rglru_block_with_state(hh, lp["rec"], cfg)
                h = h + y
                outs[f"b{idx}_conv"] = final["conv"]
                outs[f"b{idx}_h"] = final["h"]
            else:
                h, (k, v) = _attention_mix(h, lp, cfg, positions, window=cfg.local_window)
                outs[f"b{idx}_k"] = _pad_cap(k, W).astype(jnp.bfloat16)
                outs[f"b{idx}_v"] = _pad_cap(v, W).astype(jnp.bfloat16)
            hh = rmsnorm(h, lp["ln2"], cfg.norm_eps)
            h = h + swiglu(hh, lp["mlp"])
        return h, outs

    x, group_caches = lax.scan(group_body, x, params["groups"])
    for key, val in group_caches.items():
        cache[key] = val

    if "tail" in params:
        t = 0
        for key, tp in params["tail"].items():
            kind = key.split("_", 1)[1]
            if key.startswith("t_"):  # stacked homogeneous tail (rec only)
                def tail_body(carry, lp):
                    h = carry
                    hh = rmsnorm(h, lp["ln1"], cfg.norm_eps)
                    y, final = _rglru_block_with_state(hh, lp["rec"], cfg)
                    h = h + y
                    hh = rmsnorm(h, lp["ln2"], cfg.norm_eps)
                    h = h + swiglu(hh, lp["mlp"])
                    return h, final

                x, finals = lax.scan(tail_body, x, tp)
                n_tail = finals["h"].shape[0]
                for i in range(n_tail):
                    cache[f"t{i}_conv"] = finals["conv"][i]
                    cache[f"t{i}_h"] = finals["h"][i]
            else:
                raise NotImplementedError("heterogeneous hybrid tail")
            t += 1
    return x, cache


def _rglru_block_with_state(x, p, cfg):
    from .rglru import C_FACTOR, _rglru_gates
    from .ssm import _causal_conv1d

    B, S, _ = x.shape
    K = cfg.d_conv
    gate = jax.nn.gelu(
        jnp.einsum("bsd,dw->bsw", x, p["in_gate"]).astype(jnp.float32)
    ).astype(x.dtype)
    xs_raw = jnp.einsum("bsd,dw->bsw", x, p["in_x"])
    xs = _causal_conv1d(xs_raw, p["conv_w"], p["conv_b"])
    softplus_neg_lam = jax.nn.softplus(-p["lam"].astype(jnp.float32))
    r, i = _rglru_gates(xs, p)

    def step(h, inputs):
        x_t, r_t, i_t = inputs
        a = jnp.exp(-C_FACTOR * r_t * softplus_neg_lam)
        h = a * h + jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (
            i_t * x_t.astype(jnp.float32)
        )
        return h, h.astype(x_t.dtype)

    h0 = jnp.zeros((B, cfg.lru_width_), jnp.float32)
    h_final, hs = lax.scan(
        step, h0, (xs.transpose(1, 0, 2), r.transpose(1, 0, 2), i.transpose(1, 0, 2))
    )
    y = hs.transpose(1, 0, 2) * gate
    out = jnp.einsum("bsw,wd->bsd", y, p["out"])
    conv_state = _conv_tail(xs_raw, K)
    return out, {"conv": conv_state, "h": h_final}


def _first_attn_idx(cfg: ModelConfig) -> Optional[int]:
    for idx, kind in enumerate(cfg.block_pattern):
        if kind == "attn":
            return idx
    return None


# ---------------------------------------------------------------------------
# Decode — one token through the stack with cache update
# ---------------------------------------------------------------------------


def decode_step(
    params: Dict[str, Any],
    tokens: jnp.ndarray,  # (B, 1)
    cache: Dict[str, Any],
    cfg: ModelConfig,
    positions: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Returns (logits (B, V) fp32, updated cache)."""
    pos_now = cache["len"]  # scalar int32 — position of the incoming token
    x, positions = decode_inputs(params, tokens, pos_now, cfg, positions)

    if cfg.family in ("dense", "vlm", "moe"):
        def body(h, inputs):
            lp, ck, cv = inputs
            h, kv, _ = decode_attn_layer(h, lp, ck, cv, cfg, positions, pos_now)
            return h, kv

        x, (ks, vs) = lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
        new_cache = {"k": ks, "v": vs, "len": pos_now + 1}
    elif cfg.family == "ssm":
        def body(h, inputs):
            lp, conv, hstate = inputs
            hh = rmsnorm(h, lp["ln"], cfg.norm_eps)
            y, nc = ssm_decode_step(hh, {"conv": conv, "h": hstate}, lp["ssm"], cfg)
            return h + y, (nc["conv"], nc["h"])

        x, (convs, hs) = lax.scan(body, x, (params["layers"], cache["conv"], cache["h"]))
        new_cache = {"conv": convs, "h": hs, "len": pos_now + 1}
    elif cfg.family == "hybrid":
        x, new_cache = _hybrid_decode(params, x, cache, cfg, positions, pos_now)
        new_cache["len"] = pos_now + 1
    else:
        raise ValueError(cfg.family)

    return decode_logits(params, x, cfg), new_cache


def decode_inputs(
    params: Dict[str, Any],
    tokens: jnp.ndarray,  # (B, 1)
    pos_now: jnp.ndarray,
    cfg: ModelConfig,
    positions: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The embedded token and its positions (every row at ``pos_now``
    unless ``positions`` is given) for a one-token decode."""
    B = tokens.shape[0]
    if positions is None:
        pos = jnp.broadcast_to(pos_now, (B, 1)).astype(jnp.int32)
        positions = jnp.broadcast_to(pos, (3, B, 1)) if cfg.mrope else pos
    return embed(tokens, params["embed"]), positions


def decode_attn_layer(h, lp, ck, cv, cfg: ModelConfig, positions, pos_now):
    """One dense / vlm / moe layer of a one-token decode.

    The token's K and V go into slot ``pos_now`` of the layer's cache rows
    ``ck``/``cv`` (B, cap, kv, hd), the query attends over their first
    ``pos_now + 1`` slots, then the MLP or the MoE block runs.  Returns
    ``(h, (ck, cv), (k, v))``: the updated rows, and the new slot's K and V
    as stored (B, 1, kv, hd) for a caller that keeps the rows elsewhere.
    """
    hh = rmsnorm(h, lp["ln1"], cfg.norm_eps)
    q, k, v = project_qkv(hh, lp["attn"], cfg, positions)
    k, v = k.astype(ck.dtype), v.astype(cv.dtype)
    ck = lax.dynamic_update_slice_in_dim(ck, k, pos_now, axis=1)
    cv = lax.dynamic_update_slice_in_dim(cv, v, pos_now, axis=1)
    o = decode_attention(q, ck, cv, pos_now + 1)
    h = h + output_proj(o, lp["attn"])
    hh = rmsnorm(h, lp["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        delta, _ = moe_block(hh, lp["moe"], cfg)
    else:
        delta = swiglu(hh, lp["mlp"])
    return h + delta, (ck, cv), (k, v)


def decode_logits(params: Dict[str, Any], x: jnp.ndarray, cfg: ModelConfig):
    """Final norm and unembedding of a one-token decode: (B, V) fp32."""
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _logits(params, x, cfg)[:, 0]


def _hybrid_decode(params, x, cache, cfg, positions, pos_now):
    period = len(cfg.block_pattern)
    new_cache: Dict[str, Any] = {}

    def one_layer(h, kind, lp, lcache):
        out_cache = {}
        if kind == "rec":
            hh = rmsnorm(h, lp["ln1"], cfg.norm_eps)
            y, nc = rglru_decode_step(
                hh, {"conv": lcache["conv"], "h": lcache["h"]}, lp["rec"], cfg
            )
            h = h + y
            out_cache["conv"], out_cache["h"] = nc["conv"], nc["h"]
        else:
            hh = rmsnorm(h, lp["ln1"], cfg.norm_eps)
            q, k, v = project_qkv(hh, lp["attn"], cfg, positions)
            W = lcache["k"].shape[1]
            slot = jnp.mod(pos_now, W)
            ck = lax.dynamic_update_slice_in_dim(
                lcache["k"], k.astype(lcache["k"].dtype), slot, axis=1
            )
            cv = lax.dynamic_update_slice_in_dim(
                lcache["v"], v.astype(lcache["v"].dtype), slot, axis=1
            )
            n_valid = jnp.minimum(pos_now + 1, W)
            o = decode_attention(q, ck, cv, n_valid)
            h = h + output_proj(o, lp["attn"])
            out_cache["k"], out_cache["v"] = ck, cv
        hh = rmsnorm(h, lp["ln2"], cfg.norm_eps)
        h = h + swiglu(hh, lp["mlp"])
        return h, out_cache

    def group_body(h, inputs):
        gp = inputs["params"]
        outs = {}
        for idx, kind in enumerate(cfg.block_pattern):
            lp = gp[f"b{idx}_{kind}"]
            if kind == "rec":
                lc = {"conv": inputs[f"b{idx}_conv"], "h": inputs[f"b{idx}_h"]}
            else:
                lc = {"k": inputs[f"b{idx}_k"], "v": inputs[f"b{idx}_v"]}
            h, oc = one_layer(h, kind, lp, lc)
            for kk, vv in oc.items():
                outs[f"b{idx}_{kk}"] = vv
        return h, outs

    xs_tree = {"params": params["groups"]}
    for key in cache:
        if key.startswith("b"):
            xs_tree[key] = cache[key]
    x, group_out = lax.scan(group_body, x, xs_tree)
    new_cache.update(group_out)

    if "tail" in params:
        for key, tp in params["tail"].items():
            if key.startswith("t_"):  # stacked rec tail
                def tail_body(h, inputs):
                    lp, conv, hstate = inputs
                    hh = rmsnorm(h, lp["ln1"], cfg.norm_eps)
                    y, nc = rglru_decode_step(
                        hh, {"conv": conv, "h": hstate}, lp["rec"], cfg
                    )
                    h = h + y
                    hh = rmsnorm(h, lp["ln2"], cfg.norm_eps)
                    h = h + swiglu(hh, lp["mlp"])
                    return h, (nc["conv"], nc["h"])

                n_tail = jax.tree.leaves(tp)[0].shape[0]
                convs = jnp.stack([cache[f"t{i}_conv"] for i in range(n_tail)])
                hs = jnp.stack([cache[f"t{i}_h"] for i in range(n_tail)])
                x, (nconvs, nhs) = lax.scan(tail_body, x, (tp, convs, hs))
                for i in range(n_tail):
                    new_cache[f"t{i}_conv"] = nconvs[i]
                    new_cache[f"t{i}_h"] = nhs[i]
            else:
                raise NotImplementedError("heterogeneous hybrid tail")
    return x, new_cache
