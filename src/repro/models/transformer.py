"""Model assembly for all six families with three entry points:

    train_forward(cfg, params, tokens, ...)    -> logits, aux
    prefill(cfg, params, tokens, cache, ...)   -> logits, cache, aux
    decode_step(cfg, params, cache, tokens,..) -> logits, cache, aux

Uniform-kind architectures (dense / moe / ssm / audio / vlm) stack per-layer
params with a leading L dim and run `lax.scan` over layers, keeping compile
time O(1) in depth (the 61-layer Kimi-K2 config must compile on one CPU core
with 512 host devices for the dry-run). An MoE with leading dense layers
(DeepSeek-V2's `first_k_dense`) stacks and scans those as a group of their
own (`dense_blocks`, cache entries `dense_*`) ahead of the MoE `blocks`.
The hybrid pattern architecture (RecurrentGemma "RRA") uses a python loop
over its 38 heterogeneous layers.

KV caches are ring buffers: ring size = full length for full attention, or
window + SPEC_PAD for sliding-window variants, so `long_500k` decode on a
windowed model allocates O(window), not O(seq). Speculative rollback is a
pure metadata operation for attention caches and an indexed select into
staged states for recurrent caches (`rollback_cache`)."""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import layers as L
from . import mla as mla_mod
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import rwkv as rwkv_mod

SPEC_PAD = 16  # ring-buffer slack so speculative writes never clobber window
DENSE = "dense_"  # cache-entry prefix of the leading dense layers' group


# ===================================================================== #
# Parameter init
# ===================================================================== #

def _init_block(cfg, kind: str, key, dtype):
    ks = jax.random.split(key, 6)
    if kind == "W":  # rwkv
        return {
            "ln1": L.init_norm(cfg, cfg.d_model, dtype),
            "tmix": rwkv_mod.init_time_mix(cfg, ks[0], dtype),
            "ln2": L.init_norm(cfg, cfg.d_model, dtype),
            "cmix": rwkv_mod.init_channel_mix(cfg, ks[1], dtype),
        }
    if kind == "R":  # rg-lru recurrent block + ffn
        return {
            "ln1": L.init_norm(cfg, cfg.d_model, dtype),
            "rec": rglru_mod.init_rglru_block(cfg, ks[0], dtype),
            "ln2": L.init_norm(cfg, cfg.d_model, dtype),
            "ffn": L.init_mlp(cfg, ks[1], cfg.d_model, cfg.d_ff, dtype),
        }
    # attention-bearing kinds
    p = {"ln1": L.init_norm(cfg, cfg.d_model, dtype)}
    p["attn"] = (mla_mod.init_mla(cfg, ks[0], dtype) if cfg.use_mla
                 else attn_mod.init_attention(cfg, ks[0], dtype))
    if kind == "X":
        p["lnx"] = L.init_norm(cfg, cfg.d_model, dtype)
        p["xattn"] = attn_mod.init_cross_attention(cfg, ks[1], dtype)
    p["ln2"] = L.init_norm(cfg, cfg.d_model, dtype)
    if cfg.is_moe and kind != "D":
        p["moe"] = moe_mod.init_moe(cfg, ks[2], dtype)
    else:
        p["ffn"] = L.init_mlp(cfg, ks[2], cfg.d_model, cfg.d_ff, dtype)
    return p


def init_params(cfg, key):
    dtype = jnp.dtype(cfg.dtype)
    kinds = cfg.layer_kinds()
    k_embed, k_blocks = jax.random.split(key)
    params: Dict[str, Any] = {"embed": L.init_embed(cfg, k_embed, dtype)}
    n_dense = cfg.first_k_dense
    if len(set(kinds[n_dense:])) == 1:  # uniform: stacked params + scan
        keys = jax.random.split(k_blocks, cfg.num_layers)
        if n_dense:
            params["dense_blocks"] = jax.vmap(
                lambda k: _init_block(cfg, "D", k, dtype))(keys[:n_dense])
        params["blocks"] = jax.vmap(
            lambda k: _init_block(cfg, kinds[-1], k, dtype))(keys[n_dense:])
    else:
        keys = jax.random.split(k_blocks, cfg.num_layers)
        params["blocks_list"] = tuple(
            _init_block(cfg, kind, k, dtype) for kind, k in zip(kinds, keys))
    params["final_norm"] = L.init_norm(cfg, cfg.d_model, dtype)
    return params


# ===================================================================== #
# Cache
# ===================================================================== #

def ring_size(cfg, max_len: int, window: int) -> int:
    """Ring slots for a sliding-window cache: `window + SPEC_PAD` live slots
    (modulus) so writing position p only ever evicts p-window-SPEC_PAD —
    outside the window for every in-flight query — plus SPEC_PAD spill slots
    so a contiguous dynamic-update-slice write never wraps."""
    if window and window > 0:
        return min(max_len, window + 2 * SPEC_PAD)
    return max_len


def init_cache(cfg, batch: int, max_len: int, *, window: int = 0,
               dtype=None, per_row: bool = False):
    """Allocate an empty cache for `batch` sequences of up to `max_len`
    tokens. `window` (0=full) selects sliding-window attention and sizes the
    ring buffer accordingly.

    `per_row=True` adds a `lengths` [B] vector so every row keeps its own
    sequence length — the continuous-batching layout where rows join, draft
    different K_i, and roll back independently. The scalar `length` is kept
    alongside (as the row maximum) for code that only needs an upper bound."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    kinds = cfg.layer_kinds()
    cache: Dict[str, Any] = {
        "length": jnp.zeros((), jnp.int32),
    }
    if per_row:
        cache["lengths"] = jnp.zeros((batch,), jnp.int32)
    n_attn = sum(1 for k in kinds if k in ("A", "X"))
    n_dense = sum(1 for k in kinds if k == "D")
    n_rec = sum(1 for k in kinds if k == "R")
    n_rwkv = sum(1 for k in kinds if k == "W")

    if n_attn:
        w_eff = window if window else (cfg.window or 0)
        r = ring_size(cfg, max_len, w_eff)
        cache["pos"] = jnp.full((batch, r), -1, jnp.int32)
        for prefix, n in (("", n_attn), (DENSE, n_dense)):
            if not n:
                continue
            if cfg.use_mla:
                cache[prefix + "ckv"] = jnp.zeros(
                    (n, batch, r, cfg.kv_lora_rank), dtype)
                cache[prefix + "krope"] = jnp.zeros(
                    (n, batch, r, cfg.qk_rope_dim), dtype)
            else:
                hkv, hd = cfg.num_kv_heads, cfg.head_dim
                cache[prefix + "k"] = jnp.zeros((n, batch, r, hkv, hd), dtype)
                cache[prefix + "v"] = jnp.zeros((n, batch, r, hkv, hd), dtype)
        if cfg.is_encoder_decoder:
            cache["enc_k"] = jnp.zeros(
                (n_attn, batch, cfg.encoder_len, cfg.num_heads, cfg.head_dim), dtype)
            cache["enc_v"] = jnp.zeros_like(cache["enc_k"])
    if n_rwkv:
        h, n = cfg.rwkv_num_heads, cfg.rwkv_head_size
        cache["wkv"] = jnp.zeros((n_rwkv, batch, h, n, n), jnp.float32)
        cache["sx_att"] = jnp.zeros((n_rwkv, batch, cfg.d_model), dtype)
        cache["sx_ffn"] = jnp.zeros((n_rwkv, batch, cfg.d_model), dtype)
    if n_rec:
        cache["h"] = jnp.zeros((n_rec, batch, cfg.d_rnn), jnp.float32)
        cache["conv"] = jnp.zeros(
            (n_rec, batch, cfg.conv1d_width - 1, cfg.d_rnn), dtype)
    return cache


def bucket_length(t: int, minimum: int = 1) -> int:
    """Round a span length up to the next power of two. Chunked prefill pads
    every [B, T] pass to a bucketed T so the jitted pass is traced once per
    bucket instead of once per distinct prompt/chunk length — the blocking
    prefill's retrace-per-prompt-length pathology does not come back through
    the chunked path."""
    t = max(int(t), int(minimum), 1)
    return 1 << (t - 1).bit_length()


def cache_slots(cache, positions_1d):
    """Map absolute positions [T] to ring slots [T]."""
    r = cache["pos"].shape[1]
    return positions_1d % r


def rollback_cache(cfg, cache, staged, n_accept, length_before):
    """Rewind the cache to `length_before + n_accept` after verification.

    Attention caches: metadata-only (invalidate pos of rejected slots).
    Recurrent caches: select the staged state at index n_accept.

    Scalar `n_accept`/`length_before` rewind every row uniformly (the legacy
    single-request path). [B]-shaped arrays rewind each row to its own
    accepted length — one vectorized truncation for the whole batch, the
    continuous-batching equivalent of B independent rollbacks."""
    n_accept = jnp.asarray(n_accept, jnp.int32)
    length_before = jnp.asarray(length_before, jnp.int32)
    new_len = length_before + n_accept
    cache = dict(cache)
    if new_len.ndim == 0:
        cache["length"] = new_len
        if "lengths" in cache:
            cache["lengths"] = jnp.broadcast_to(new_len,
                                                cache["lengths"].shape)
        row_len = new_len          # broadcasts over [B,R] pos
        staged_idx = n_accept      # same staged index for every row
    else:
        cache["lengths"] = new_len
        cache["length"] = jnp.max(new_len)
        row_len = new_len[:, None]
        staged_idx = None
    if "pos" in cache:
        cache["pos"] = jnp.where(cache["pos"] >= row_len, -1, cache["pos"])
    if staged:
        for name in ("wkv", "sx_att", "sx_ffn", "h", "conv"):
            if name in staged and staged[name] is not None:
                st = staged[name]  # [L, T+1, B, ...]
                if staged_idx is not None:
                    sel = jnp.take(st, staged_idx, axis=1)
                else:
                    # per-row gather: row b keeps the state after consuming
                    # its own n_accept[b] tokens
                    sel = st[:, n_accept, jnp.arange(n_accept.shape[0])]
                cache[name] = sel.astype(cache[name].dtype)
    return cache


def write_cache_row(cache, slot: int, row_cache):
    """Copy a batch-1 cache (e.g. a freshly prefilled request) into row
    `slot` of a per-row batched cache — the join half of continuous
    batching. Both caches must share ring size / layer layout."""
    out = dict(cache)
    for name, buf in cache.items():
        if name in ("length", "lengths"):
            continue
        src = row_cache[name]
        if name == "pos":                       # [B,R] <- [1,R]
            out[name] = buf.at[slot].set(src[0])
        else:                                   # [L,B,...] <- [L,1,...]
            out[name] = buf.at[:, slot].set(src[:, 0].astype(buf.dtype))
    row_len = (row_cache["lengths"][0] if "lengths" in row_cache
               else row_cache["length"])
    if "lengths" in cache:
        lengths = cache["lengths"].at[slot].set(row_len)
        out["lengths"] = lengths
        out["length"] = jnp.max(lengths)
    else:
        out["length"] = jnp.maximum(cache["length"], row_len)
    return out


def clear_cache_row(cache, slot: int):
    """Retire row `slot`: zero its length and invalidate its ring positions
    (stale K/V content is masked out by pos == -1, no data wipe needed)."""
    out = dict(cache)
    if "pos" in cache:
        out["pos"] = cache["pos"].at[slot].set(-1)
    if "lengths" in cache:
        lengths = cache["lengths"].at[slot].set(0)
        out["lengths"] = lengths
        out["length"] = jnp.max(lengths)
    return out


# ===================================================================== #
# Block application
# ===================================================================== #

def _write_ring(buf_l, vals, wctx):
    """Write T new entries into a cache buffer [B,R,...].

    Three modes (wctx from _forward):
      * slots scatter (baseline): buf.at[:, slots].set(vals) — one slot
        vector shared by every row
      * per-row scatter (continuous batching): rows sit at different
        lengths, so row b writes to its own slots_bt[b] ring positions
      * contiguous dynamic_update_slice (§Perf "dus-cache"): in-place, no
        SPMD resharding copy — the scatter path triggers XLA "involuntary
        full rematerialization" of the whole stacked cache per layer."""
    vals = vals.astype(buf_l.dtype)
    if wctx.get("offset") is not None:
        starts = (jnp.zeros((), jnp.int32), wctx["offset"]) + tuple(
            jnp.zeros((), jnp.int32) for _ in range(buf_l.ndim - 2))
        return jax.lax.dynamic_update_slice(buf_l, vals, starts)
    if wctx.get("slots_bt") is not None:
        slots_bt = wctx["slots_bt"]                       # [B,T]
        rows = jnp.arange(slots_bt.shape[0])[:, None]     # [B,1]
        return buf_l.at[rows, slots_bt].set(vals)
    return buf_l.at[:, wctx["slots"]].set(vals)


def _attn_block(cfg, p, x, lc, ctx, kind):
    """Attention(+cross)(+ffn/moe) block.

    lc: layer cache dict ({"k","v"} or {"ckv","krope"}, + enc_*) or None.
    ctx: dict with mode, seq_pos [B,T], rope_pos, cache_pos [B,R] (updated),
         slots [T], window, enc_out.
    Returns (x, new_layer_cache, aux)."""
    mode = ctx["mode"]
    window = ctx["window"]
    seq_pos, rope_pos = ctx["seq_pos"], ctx["rope_pos"]
    with jax.named_scope("attention"):
        h = L.apply_norm(cfg, p["ln1"], x)
        new_lc = {}
        if cfg.use_mla:
            if mode == "decode":
                ckv_new, krope_new = mla_mod.latent_kv(cfg, p["attn"], h,
                                                       seq_pos)
                ckv = _write_ring(lc["ckv"], ckv_new, ctx)
                krope = _write_ring(lc["krope"], krope_new, ctx)
                out = mla_mod.mla_absorbed(cfg, p["attn"], h, seq_pos, ckv,
                                           krope, ctx["cache_pos"],
                                           window=window)
                new_lc.update(ckv=ckv, krope=krope)
            else:
                out, (ckv_new, krope_new) = mla_mod.mla_full(
                    cfg, p["attn"], h, seq_pos)
                if mode == "prefill":
                    t_w = ctx["t_w"]
                    new_lc["ckv"] = _write_ring(lc["ckv"], ckv_new[:, -t_w:],
                                                ctx)
                    new_lc["krope"] = _write_ring(lc["krope"],
                                                  krope_new[:, -t_w:], ctx)
        else:
            q, k, v = attn_mod.qkv(cfg, p["attn"], h, rope_pos)
            if mode == "decode":
                kb = _write_ring(lc["k"], k, ctx)
                vb = _write_ring(lc["v"], v, ctx)
                out = attn_mod.attend(q, kb.astype(q.dtype),
                                      vb.astype(q.dtype), seq_pos,
                                      ctx["cache_pos"], window=window,
                                      causal=True)
                new_lc.update(k=kb, v=vb)
            else:
                out = attn_mod.attend(q, k, v, seq_pos, seq_pos,
                                      window=window, causal=True)
                if mode == "prefill":
                    t_w = ctx["t_w"]
                    new_lc["k"] = _write_ring(lc["k"], k[:, -t_w:], ctx)
                    new_lc["v"] = _write_ring(lc["v"], v[:, -t_w:], ctx)
            b, t = out.shape[:2]
            out = out.reshape(b, t, -1) @ p["attn"]["wo"]
    x = x + out

    if kind == "X":  # cross-attention to (stub) encoder states
        hx = L.apply_norm(cfg, p["lnx"], x)
        if mode == "prefill":
            enc_k, enc_v = attn_mod.encode_cross_kv(cfg, p["xattn"],
                                                    ctx["enc_out"])
            new_lc["enc_k"], new_lc["enc_v"] = enc_k, enc_v
        elif mode == "decode":
            enc_k, enc_v = lc["enc_k"], lc["enc_v"]
            new_lc["enc_k"], new_lc["enc_v"] = enc_k, enc_v
        else:  # train
            enc_k, enc_v = attn_mod.encode_cross_kv(cfg, p["xattn"],
                                                    ctx["enc_out"])
        x = x + attn_mod.cross_attention(cfg, p["xattn"], hx,
                                         enc_k.astype(hx.dtype),
                                         enc_v.astype(hx.dtype))

    h2 = L.apply_norm(cfg, p["ln2"], x)
    aux = {}
    if "moe" in p:
        b, t, d = h2.shape
        with jax.named_scope("moe_ffn"):
            y2d, moe_aux = moe_mod.apply_moe(
                cfg, p["moe"], h2.reshape(b * t, d),
                capacity_policy=ctx["moe_policy"],
                packed=ctx.get("moe_packed", False))
        x = x + y2d.reshape(b, t, d)
        aux["lb_loss"] = moe_aux["lb_loss"]
        aux["unique_experts"] = moe_aux["unique_experts"]
        if mode == "decode" and "expert_idx" in moe_aux:
            # batch-aware accounting: per-row counts always; the union
            # replaces the raw all-token count when a padding mask marks
            # ragged [1+K_i] spans (padding must not inflate the cost driver)
            idx_btk = moe_aux["expert_idx"].reshape(b, t, -1)
            union, per_row = moe_mod.unique_expert_stats(
                cfg, idx_btk, ctx.get("token_mask"))
            aux["unique_experts_row"] = per_row
            if ctx.get("token_mask") is not None:
                aux["unique_experts"] = union
            # per-expert activation bitmap [E] for residency tracking
            # (docs/offload.md): padding routes to the sentinel bucket e
            e = cfg.num_experts
            flat = idx_btk
            if ctx.get("token_mask") is not None:
                flat = jnp.where(ctx["token_mask"][:, :, None], idx_btk, e)
            hits = jnp.zeros((e + 1,), jnp.int32).at[
                flat.reshape(-1)].add(1)
            aux["experts_active"] = hits[:e] > 0
            if ctx.get("want_moe_h"):
                # the MoE input (post-ln2 hidden state) feeding this
                # layer's router — the layered prefetcher probes NEXT
                # pass's per-layer routing from it (docs/offload.md)
                aux["moe_h"] = h2
            sid = ctx.get("ep_shard_ids")
            if sid is not None:
                # EP-shard accounting: the hottest shard's local activated
                # experts gate a sharded pass (docs/expert_parallel.md)
                per_shard, row_shard = moe_mod.shard_expert_stats(
                    cfg, idx_btk, sid, ctx.get("token_mask"),
                    n_shards=ctx.get("ep_n_shards"))
                aux["unique_experts_shard"] = per_shard
                aux["unique_experts_row_shard"] = row_shard
    else:
        with jax.named_scope("dense_ffn"):
            y = L.apply_mlp(cfg, p["ffn"], h2)
        x = x + y
        aux["lb_loss"] = jnp.zeros((), jnp.float32)
        aux["unique_experts"] = jnp.zeros((), jnp.int32)
        if mode == "decode":
            aux["unique_experts_row"] = jnp.zeros((x.shape[0],), jnp.int32)
            aux["experts_active"] = jnp.zeros((cfg.num_experts,), bool)
            sid = ctx.get("ep_shard_ids")
            if sid is not None:
                s_n = (int(ctx["ep_n_shards"]) if ctx.get("ep_n_shards")
                       else int(max(sid)) + 1)
                aux["unique_experts_shard"] = jnp.zeros((s_n,), jnp.int32)
                aux["unique_experts_row_shard"] = jnp.zeros(
                    (x.shape[0], s_n), jnp.int32)
    return x, new_lc, aux


def _rwkv_block(cfg, p, x, lc, ctx):
    mode = ctx["mode"]
    want = mode == "decode"
    h = L.apply_norm(cfg, p["ln1"], x)
    if mode == "train":
        b = x.shape[0]
        sx_att = jnp.zeros((b, cfg.d_model), x.dtype)
        sx_ffn = jnp.zeros((b, cfg.d_model), x.dtype)
        s0 = jnp.zeros((b, cfg.rwkv_num_heads, cfg.rwkv_head_size,
                        cfg.rwkv_head_size), jnp.float32)
    else:
        sx_att, sx_ffn, s0 = lc["sx_att"], lc["sx_ffn"], lc["wkv"]
    out, last_x, s_last, states = rwkv_mod.time_mix(
        cfg, p["tmix"], h, sx_att.astype(h.dtype), s0, want_states=want)
    x = x + out
    h2 = L.apply_norm(cfg, p["ln2"], x)
    out2, last_x2 = rwkv_mod.channel_mix(cfg, p["cmix"], h2,
                                         sx_ffn.astype(h2.dtype))
    x = x + out2
    new_lc = {"wkv": s_last, "sx_att": last_x, "sx_ffn": last_x2}
    staged = None
    if want:
        # staged token-shift states: value after consuming j tokens
        sx_att_staged = jnp.concatenate(
            [sx_att.astype(h.dtype)[None], jnp.moveaxis(h, 1, 0)], axis=0)
        sx_ffn_staged = jnp.concatenate(
            [sx_ffn.astype(h2.dtype)[None], jnp.moveaxis(h2, 1, 0)], axis=0)
        staged = {"wkv": states, "sx_att": sx_att_staged,
                  "sx_ffn": sx_ffn_staged}
    return x, new_lc, staged


def _rec_block(cfg, p, x, lc, ctx):
    mode = ctx["mode"]
    want = mode == "decode"
    h = L.apply_norm(cfg, p["ln1"], x)
    if mode == "train":
        b = x.shape[0]
        state = {"h": jnp.zeros((b, cfg.d_rnn), jnp.float32),
                 "conv": jnp.zeros((b, cfg.conv1d_width - 1, cfg.d_rnn),
                                   x.dtype)}
    else:
        state = {"h": lc["h"], "conv": lc["conv"]}
    out, new_state, staged = rglru_mod.apply_rglru_block(
        cfg, p["rec"], h, state, want_states=want)
    x = x + out
    h2 = L.apply_norm(cfg, p["ln2"], x)
    x = x + L.apply_mlp(cfg, p["ffn"], h2)
    return x, new_state, staged


# ===================================================================== #
# Forward passes
# ===================================================================== #

def _sinusoid(positions, dim):
    """[B,T] -> [B,T,dim] sinusoidal embedding (whisper decoder positions)."""
    half = dim // 2
    freq = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _embed_inputs(cfg, params, tokens, embeds, seq_pos):
    if embeds is None:
        embeds = L.embed_tokens(params["embed"], tokens)
    if cfg.is_encoder_decoder:  # whisper-style learned/sinusoid positions
        embeds = embeds + _sinusoid(seq_pos, cfg.d_model).astype(embeds.dtype)
    return embeds


def _layer_cache_slice(cfg, cache, mode, kind, prefix=""):
    """The stacked cache entries of one group of `kind` layers (entries
    named `prefix` + name), keyed by name, for scan xs."""
    if cache is None or mode == "train":
        return None
    names = {
        "A": ["k", "v"] if not cfg.use_mla else ["ckv", "krope"],
        "X": ["k", "v", "enc_k", "enc_v"],
        "W": ["wkv", "sx_att", "sx_ffn"],
    }["A" if kind == "D" else kind]
    return {n: cache[prefix + n] for n in names if prefix + n in cache}


def _run_uniform(cfg, blocks, kind, x, lc_stack, ctx):
    """lax.scan over stacked homogeneous layers."""
    mode = ctx["mode"]

    def body(carry, xs):
        h = carry
        from repro.distributed.sharding import constrain as _con, opt as _po
        if _po("residual-shard"):
            # §Perf: 2-D activation sharding — remat-stored residuals live
            # (batch over data) x (d_model over model) instead of replicated
            # over the model axis
            h = _con(h, ("pod", "data"), None, "model")
        p_l, lc_l = xs
        if kind == "W":
            h, new_lc, staged = _rwkv_block(cfg, p_l, h, lc_l, ctx)
            aux = {}
        else:
            h, new_lc, aux = _attn_block(cfg, p_l, h, lc_l, ctx, kind)
            staged = None
        ys = {"cache": new_lc, "staged": staged, "aux": aux}
        ys = {k: v for k, v in ys.items() if v}
        return h, ys

    if mode == "train":
        body = jax.checkpoint(body)
    xs = (blocks, lc_stack)
    x, ys = jax.lax.scan(body, x, xs)
    return x, ys


def _run_pattern(cfg, params, x, cache, ctx):
    """Python loop over heterogeneous layers (hybrid RecurrentGemma)."""
    kinds = cfg.layer_kinds()
    mode = ctx["mode"]
    i_rec = i_attn = 0
    new_rec = {"h": [], "conv": []}
    new_attn = {"k": [], "v": []}
    staged_rec = {"h": [], "conv": []}
    for kind, p_l in zip(kinds, params["blocks_list"]):
        if kind == "R":
            lc = (None if cache is None or mode == "train" else
                  {"h": cache["h"][i_rec], "conv": cache["conv"][i_rec]})
            if mode == "train":
                x = jax.checkpoint(
                    lambda p, h: _rec_block(cfg, p, h, None, ctx)[0])(p_l, x)
                st = staged = None
            else:
                x, st, staged = _rec_block(cfg, p_l, x, lc, ctx)
                new_rec["h"].append(st["h"])
                new_rec["conv"].append(st["conv"])
            if staged is not None:
                staged_rec["h"].append(staged["h"])
                staged_rec["conv"].append(staged["conv"])
            i_rec += 1
        else:  # local attention layer
            lc = (None if cache is None or mode == "train" else
                  {"k": cache["k"][i_attn], "v": cache["v"][i_attn]})
            lctx = dict(ctx, window=cfg.local_window)
            if mode == "train":
                x = jax.checkpoint(
                    lambda p, h: _attn_block(cfg, p, h, None, lctx, "A")[0])(p_l, x)
            else:
                x, new_lc, _ = _attn_block(cfg, p_l, x, lc, lctx, "A")
                new_attn["k"].append(new_lc["k"])
                new_attn["v"].append(new_lc["v"])
            i_attn += 1
    ys = {}
    if mode != "train":
        ys["cache"] = {}
        if new_rec["h"]:
            ys["cache"]["h"] = jnp.stack(new_rec["h"])
            ys["cache"]["conv"] = jnp.stack(new_rec["conv"])
        if new_attn["k"]:
            ys["cache"]["k"] = jnp.stack(new_attn["k"])
            ys["cache"]["v"] = jnp.stack(new_attn["v"])
    if staged_rec["h"]:
        ys["staged"] = {"h": jnp.stack(staged_rec["h"]),
                        "conv": jnp.stack(staged_rec["conv"])}
    return x, ys


def _forward(cfg, params, tokens, *, embeds, cache, mode, seq_pos, rope_pos,
             window, enc_out, moe_exact, token_mask=None, ep_shard_ids=None,
             ep_n_shards=None, moe_packed=False, want_moe_h=False):
    x = _embed_inputs(cfg, params, tokens, embeds, seq_pos)
    n_inflight = x.shape[0] * x.shape[1]
    if not moe_exact:
        moe_policy = "train"
    elif n_inflight <= 64:
        moe_policy = "exact"     # single-request verification: bit-exact
    else:
        from repro.distributed.sharding import opt as _opt
        moe_policy = "serve" if _opt("serve-capacity") else "exact"
    from repro.distributed.sharding import opt as _perf_opt
    # per-row layout: rows sit at independent lengths, so ring slots (and
    # pos updates) are computed per row rather than shared across the batch
    per_row = cache is not None and "lengths" in cache
    ctx = {"mode": mode, "seq_pos": seq_pos, "rope_pos": rope_pos,
           "window": window, "enc_out": enc_out, "moe_policy": moe_policy,
           "cache_pos": None if cache is None else cache.get("pos"),
           "slots": None, "slots_bt": None, "offset": None, "t_w": 0,
           "token_mask": token_mask, "ep_shard_ids": ep_shard_ids,
           "ep_n_shards": ep_n_shards, "moe_packed": moe_packed,
           "want_moe_h": want_moe_h}
    if cache is not None and "pos" in cache:
        t = x.shape[1]
        r = cache["pos"].shape[1]
        # effective ring modulus: ring caches (window + SPEC_PAD slots) wrap
        # at `window` so a contiguous write of <= SPEC_PAD entries never
        # splits; full caches never wrap.
        is_ring = window and r == ring_size(cfg, 1 << 62, window)
        m_eff = (r - SPEC_PAD) if is_ring else r
        t_w = min(t, m_eff)
        ctx["t_w"] = t_w
        if per_row:
            # a contiguous DUS is impossible when offsets differ per row
            ctx["slots_bt"] = seq_pos[:, -t_w:] % m_eff
        elif _perf_opt("dus-cache") and mode == "decode":
            ctx["offset"] = seq_pos[0, -t_w:][0] % m_eff
        else:
            # slot mapping uses the same modulus as the DUS path so mixed
            # prefill(scatter)/decode(DUS) runs agree on slot placement
            ctx["slots"] = seq_pos[0, -t_w:] % m_eff
        if mode in ("prefill", "decode"):
            if ctx["offset"] is not None:
                new_pos = jax.lax.dynamic_update_slice(
                    cache["pos"], seq_pos[:, -t_w:],
                    (jnp.zeros((), jnp.int32), ctx["offset"]))
            elif ctx["slots_bt"] is not None:
                rows = jnp.arange(ctx["slots_bt"].shape[0])[:, None]
                new_pos = cache["pos"].at[rows, ctx["slots_bt"]].set(
                    seq_pos[:, -t_w:])
            else:
                new_pos = cache["pos"].at[:, ctx["slots"]].set(
                    seq_pos[:, -t_w:])
            ctx["cache_pos"] = new_pos
    dense_cache = {}
    if "blocks_list" in params:
        x, ys = _run_pattern(cfg, params, x, cache, ctx)
    else:
        if "dense_blocks" in params:
            x, dys = _run_uniform(
                cfg, params["dense_blocks"], "D", x,
                _layer_cache_slice(cfg, cache, mode, "D", DENSE), ctx)
            dense_cache = {DENSE + n: v
                           for n, v in dys.get("cache", {}).items()}
        kind = cfg.layer_kinds()[-1]
        x, ys = _run_uniform(cfg, params["blocks"], kind, x,
                             _layer_cache_slice(cfg, cache, mode, kind), ctx)
    with jax.named_scope("lm_head"):
        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = L.unembed(cfg, params["embed"], x)

    aux = {}
    if "aux" in ys:
        aux["lb_loss"] = jnp.mean(ys["aux"]["lb_loss"])
        aux["unique_experts"] = ys["aux"]["unique_experts"]  # [L]
        if "unique_experts_row" in ys["aux"]:
            aux["unique_experts_row"] = ys["aux"]["unique_experts_row"]  # [L,B]
        if "experts_active" in ys["aux"]:
            aux["experts_active"] = ys["aux"]["experts_active"]  # [L,E]
        if "moe_h" in ys["aux"]:
            aux["moe_h"] = ys["aux"]["moe_h"]                    # [L,B,T,D]
        if "unique_experts_shard" in ys["aux"]:
            aux["unique_experts_shard"] = \
                ys["aux"]["unique_experts_shard"]            # [L,S]
            aux["unique_experts_row_shard"] = \
                ys["aux"]["unique_experts_row_shard"]        # [L,B,S]
    staged = ys.get("staged")

    new_cache = None
    if cache is not None and mode in ("prefill", "decode"):
        new_cache = dict(cache)
        new_cache.update(ys.get("cache", {}))
        new_cache.update(dense_cache)
        if "pos" in cache:
            new_cache["pos"] = ctx["cache_pos"]
        if per_row:
            new_cache["lengths"] = seq_pos[:, -1] + 1
            new_cache["length"] = jnp.max(new_cache["lengths"])
        else:
            new_cache["length"] = seq_pos[0, -1] + 1
    return logits, new_cache, aux, staged


# --------------------------------------------------------------------- #
# Public entry points
# --------------------------------------------------------------------- #

def train_forward(cfg, params, tokens, *, embeds=None, seq_pos=None,
                  rope_pos=None, window=0, enc_out=None, moe_exact=False):
    b, t = tokens.shape[:2] if tokens is not None else embeds.shape[:2]
    if seq_pos is None:
        seq_pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    if rope_pos is None:
        rope_pos = seq_pos
    window = window or cfg.window
    logits, _, aux, _ = _forward(cfg, params, tokens, embeds=embeds,
                                 cache=None, mode="train", seq_pos=seq_pos,
                                 rope_pos=rope_pos, window=window,
                                 enc_out=enc_out, moe_exact=moe_exact)
    return logits, aux


def prefill(cfg, params, tokens, cache, *, embeds=None, rope_pos=None,
            enc_out=None, window: int = 0, moe_exact: bool = True):
    b, t = tokens.shape[:2] if tokens is not None else embeds.shape[:2]
    seq_pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    if rope_pos is None:
        rope_pos = seq_pos
    window = window or cfg.window
    logits, cache, aux, _ = _forward(cfg, params, tokens, embeds=embeds,
                                     cache=cache, mode="prefill",
                                     seq_pos=seq_pos, rope_pos=rope_pos,
                                     window=window, enc_out=enc_out,
                                     moe_exact=moe_exact)
    return logits, cache, aux


def decode_step(cfg, params, cache, tokens, *, embeds=None, rope_pos=None,
                window: int = 0, moe_exact: bool = True, token_mask=None,
                ep_shard_ids=None, ep_n_shards=None, moe_packed=False,
                want_moe_h=False):
    """Verify/decode T tokens per row. Single-request caches start every row
    at the scalar cache['length']; per-row caches (init_cache(per_row=True))
    start row b at cache['lengths'][b], which is how a continuous batch
    verifies ragged [1+K_i] spans padded to a common T in one pass.
    `token_mask` [B,T] marks the real tokens of each span — padding tokens
    still flow through the network (their writes are rolled back) but are
    excluded from the expert-union accounting.
    `ep_shard_ids` (length-E expert -> EP shard map; see
    core/cost_model.ExpertPlacement) additionally emits per-shard and
    per-row-per-shard distinct-expert counts (`unique_experts_shard` [L,S],
    `unique_experts_row_shard` [L,B,S]) — the hottest-shard telemetry an
    EP-sharded serving deployment prices its passes with.  It may be a
    static tuple or a traced array (the engine's online replica routing
    passes one); in the traced case `ep_n_shards` must carry the static
    shard count.  `moe_packed=True` runs MoE layers on the union-packed
    verification path (see models/moe.apply_moe) — bit-identical outputs,
    union-scaled weight traffic below saturation; at saturation (U_pad ==
    E) it reads all E experts in place.  `want_moe_h=True` additionally
    returns the per-layer MoE inputs (`aux["moe_h"]` [L,B,T,D], the post-ln2
    hidden states feeding each layer's router) — the layered prefetcher's
    per-layer probe basis (docs/offload.md).
    Returns (logits [B,T,V], new_cache, aux, staged)."""
    b, t = tokens.shape[:2] if tokens is not None else embeds.shape[:2]
    offs = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    if "lengths" in cache:
        seq_pos = cache["lengths"][:, None] + offs
    else:
        seq_pos = cache["length"] + offs
    if rope_pos is None:
        rope_pos = seq_pos
    window = window or cfg.window
    logits, cache, aux, staged = _forward(cfg, params, tokens, embeds=embeds,
                                          cache=cache, mode="decode",
                                          seq_pos=seq_pos, rope_pos=rope_pos,
                                          window=window, enc_out=None,
                                          moe_exact=moe_exact,
                                          token_mask=token_mask,
                                          ep_shard_ids=ep_shard_ids,
                                          ep_n_shards=ep_n_shards,
                                          moe_packed=moe_packed,
                                          want_moe_h=want_moe_h)
    return logits, cache, aux, staged


def prefill_chunk(cfg, params, cache, tokens, *, token_mask=None,
                  rope_pos=None, window: int = 0, moe_exact: bool = True):
    """Advance cache rows by their masked prompt-chunk tokens — the chunked
    half of non-blocking admission.

    Chunked prefill is verification-shaped compute: row b's chunk enters at
    positions lengths[b]..lengths[b]+T-1, attends causally to its own cached
    context plus the in-chunk prefix, and writes its KV exactly like a
    decode span. It is therefore the decode pass with `token_mask` doing the
    ragged-chunk bookkeeping, which is what lets a serving engine pack
    prefill chunks and speculative [1+K_i] decode spans into ONE padded
    batched pass (prefill tokens then count toward the expert union — the
    paper's Fig. 2 cost driver now includes admission pressure). Callers
    roll each row back to its real chunk length, exactly like rejected
    drafts, and should pad T with `bucket_length` so jit traces are reused
    across prompt lengths.

    Returns (logits [B,T,V], new_cache, aux, staged); a row's last real
    position holds the next-token distribution once its prompt is done."""
    return decode_step(cfg, params, cache, tokens, rope_pos=rope_pos,
                       window=window, moe_exact=moe_exact,
                       token_mask=token_mask)
