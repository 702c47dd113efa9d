"""Mixture-of-Experts layer: top-k router (+optional shared experts) and a
capacity-based scatter/gather expert dispatch.

Design notes (TPU adaptation, see DESIGN.md §3):
  * Dispatch uses integer scatter/gather (zero-FLOP data movement) plus a
    stacked-expert einsum whose FLOP count is E*C*d*F with
    C = ceil(T*k/E * capacity_factor)  ==>  ~active FLOPs * capacity_factor.
    This keeps the dry-run roofline honest about MoE sparsity (a one-hot
    dispatch einsum would add a T*E*C*d term that swamps everything).
  * The routed expert indices are also returned so (a) the serving engine can
    feed *unique activated expert counts* to Cascade's cost model — the
    paper's central quantity — and (b) the Pallas `moe_gmm` kernel path can
    consume the identical routing decision.
  * Verification steps (decode) use exact capacity C=T so no token is ever
    dropped (drops would corrupt rejection sampling); training uses the
    standard GShard capacity factor with drop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import _dense_init, init_mlp, apply_mlp


def init_moe(cfg, key, dtype):
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, e), dtype, scale=0.02),
        "w_gate": _dense_init(ks[1], (e, d, f), dtype),
        "w_up": _dense_init(ks[2], (e, d, f), dtype),
        "w_down": _dense_init(ks[3], (e, f, d), dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(cfg, ks[4], d, f * cfg.num_shared_experts, dtype)
    return p


def route(cfg, p, x2d):
    """x2d: [T,d] -> (weights [T,k], idx [T,k], probs [T,E]). The weights
    are the top-k scores, divided by their sum where `cfg.norm_topk`."""
    logits = x2d.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    if cfg.router_score == "sigmoid":        # DeepSeek-V3 / Kimi-K2 style
        scores = jax.nn.sigmoid(logits)
        top, idx = jax.lax.top_k(scores, cfg.experts_per_token)
        probs = scores / (jnp.sum(scores, -1, keepdims=True) + 1e-20)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        top, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    weights = top
    if cfg.norm_topk:
        weights = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return weights, idx, probs


def load_balance_loss(cfg, probs, idx):
    """Switch-Transformer auxiliary loss: E * sum_e f_e * P_e."""
    e = cfg.num_experts
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)      # [T,k,E]
    frac_tokens = jnp.mean(jnp.sum(onehot, axis=1), axis=0)  # [E]
    frac_probs = jnp.mean(probs, axis=0)                     # [E]
    return e * jnp.sum(frac_tokens * frac_probs) / cfg.experts_per_token


def unique_expert_count(cfg, idx):
    """Number of distinct experts activated by this batch of tokens — the
    paper's data-movement driver (§2.4). idx: [T,k] -> scalar int."""
    hits = jnp.zeros((cfg.num_experts,), jnp.int32).at[idx.reshape(-1)].add(1)
    return jnp.sum(hits > 0)


def unique_expert_stats(cfg, idx_btk, token_mask=None):
    """Per-request AND batch-union distinct-expert counts — the two
    quantities batch-aware cost accounting needs (union drives the shared
    verification bytes; per-row counts drive the marginal split).

    idx_btk: [B,T,k] routed expert ids; token_mask: [B,T] bool marking the
    real (non-padding) tokens of the ragged [1+K_i] spans, or None for all
    valid. Returns (union scalar, per_row [B])."""
    b, t, k = idx_btk.shape
    e = cfg.num_experts
    if token_mask is not None:
        # padding tokens scatter into a sentinel bucket that is never counted
        idx_btk = jnp.where(token_mask[:, :, None], idx_btk, e)
    flat = idx_btk.reshape(b, t * k)
    rows = jnp.arange(b)[:, None]
    hits = jnp.zeros((b, e + 1), jnp.int32).at[rows, flat].add(1)
    per_row = jnp.sum(hits[:, :e] > 0, axis=-1)
    union = jnp.sum(jnp.sum(hits[:, :e], axis=0) > 0)
    return union, per_row


def shard_expert_stats(cfg, idx_btk, shard_of, token_mask=None,
                       n_shards=None):
    """Per-EP-shard distinct-expert counts: the batch union restricted to
    each shard's resident experts [S] and the per-row restriction [B,S] —
    the gating-shard quantities the sharded cost model prices (the pass
    completes only when the hottest shard has streamed its local activated
    experts; see core/cost_model.ExpertPlacement).

    idx_btk: [B,T,k] routed expert ids; shard_of: length-E int sequence
    mapping expert -> shard — either a static python sequence, or a traced
    array (the engine's online replica routing feeds one), in which case
    `n_shards` must be given since the shard count cannot be read off a
    tracer; token_mask: [B,T] bool marking real tokens (None = all valid).
    Because every expert lives on exactly one shard, the per-shard counts
    partition `unique_expert_stats`' union and the per-row counts
    partition its per_row."""
    b, t, k = idx_btk.shape
    e = cfg.num_experts
    s_n = int(n_shards) if n_shards is not None else int(max(shard_of)) + 1
    member = jax.nn.one_hot(jnp.asarray(shard_of, jnp.int32), s_n,
                            dtype=jnp.int32)                   # [E,S]
    if token_mask is not None:
        idx_btk = jnp.where(token_mask[:, :, None], idx_btk, e)
    flat = idx_btk.reshape(b, t * k)
    rows = jnp.arange(b)[:, None]
    hits = jnp.zeros((b, e + 1), jnp.int32).at[rows, flat].add(1)
    active = (hits[:, :e] > 0).astype(jnp.int32)               # [B,E]
    per_row_shard = active @ member                            # [B,S]
    union_active = (jnp.sum(hits[:, :e], axis=0) > 0).astype(jnp.int32)
    per_shard = union_active @ member                          # [S]
    return per_shard, per_row_shard


CAPACITY_FACTORS = {"train": 1.25, "serve": 2.0}


def _capacity(cfg, n_tokens: int, policy: str) -> int:
    """Tokens-per-expert buffer size.

    "exact":  C = T — no drop is possible (top-k experts are distinct per
              token); required for bit-exact speculative verification at
              single-request scale (the paper's single-batch setting).
    "train":  GShard capacity factor 1.25 (drops allowed, standard).
    "serve":  factor 2.0 — for batched decode/prefill, where C = T would
              make the dispatch buffer E x T x d (the §Perf kimi-decode
              finding); drop probability at 2x expected load is negligible
              and a dropped token only costs a skipped speculation."""
    if policy == "exact":
        return n_tokens
    cf = CAPACITY_FACTORS[policy]
    cap = int(n_tokens * cfg.experts_per_token * cf) // cfg.num_experts + 1
    # never below k (tiny batches) and never above T (pointless)
    return max(min(n_tokens, cap), min(n_tokens, cfg.experts_per_token))


def packed_expert_cap(cfg, n_tokens: int) -> int:
    """Static slot count U_pad of the packed verification layout.

    A T-token pass routes at most min(T*k, E) distinct experts, so the
    packed dispatch buffer needs at most that many expert slots.  The
    bound is pow-2 bucketed (reusing the span bucketing of
    `transformer.bucket_length`) so the jit trace is keyed on the same
    already-bucketed token counts the engine produces — U_pad changes only
    when the span bucket does, never per routing outcome."""
    from .transformer import bucket_length
    u = min(n_tokens * cfg.experts_per_token, cfg.num_experts)
    return min(bucket_length(u), cfg.num_experts)


def experts_in_place(cfg, p, n_tokens: int) -> bool:
    """Whether the packed path of an `n_tokens`-token pass contracts the
    stacked expert weights of `p` (one layer's or the stacked layers' MoE
    params) in place instead of gathering its union slots: true once the
    bucketed cap U_pad reaches E, where the gather would only permute all
    E experts and copy every stack, unless the experts are stored int8
    (that storage keeps its 1-byte/param gather)."""
    return ("w_up_q8" not in p
            and packed_expert_cap(cfg, n_tokens) == cfg.num_experts)


def moe_pass_counters(cfg, n_tokens: int, *, capacity_policy: str = "exact",
                      packed: bool = False, weight_bytes: int = None,
                      precision=None) -> dict:
    """Dry-run counters for one MoE layer's FFN pass: the expert-weight
    bytes the dispatch path streams and the FLOPs its stacked matmuls
    execute.  These mirror the implementation exactly — the dense path
    einsums over all E experts; the packed path gathers and multiplies
    only the U_pad = `packed_expert_cap` slots (reading all E in place
    once U_pad == E) — and back the scaling
    gates in `benchmarks/serving_micro.py --calibrate`.  Bytes price at
    the precision spec's expert class (`core.cost_model.Precision`;
    `weight_bytes` kept as a legacy uniform override) — quantized expert
    storage streams 1 byte/param."""
    if weight_bytes is None:
        from repro.core.cost_model import Precision
        weight_bytes = (precision.expert if precision is not None
                        else Precision.DEFAULT.expert)
    c = _capacity(cfg, n_tokens, capacity_policy)
    streamed = (packed_expert_cap(cfg, n_tokens) if packed
                else cfg.num_experts)
    mult = 3 if cfg.activation == "swiglu" else 2
    d, f = cfg.d_model, cfg.moe_d_ff
    return {
        "experts_streamed": streamed,
        "capacity": c,
        "expert_weight_bytes": streamed * mult * d * f * weight_bytes,
        "ffn_flops": 2.0 * streamed * c * d * f * mult,
    }


def quantize_transformer_experts(params, mode: str = "int8",
                                 quantile: float = 1.0) -> dict:
    """Quantize the routed-expert stacks of a FULL transformer params tree
    (the stacked-layer layout `transformer.init_params` builds:
    blocks/moe/w_* with a leading [L, E, ...] axis), returning a new tree.
    Scales are per-(layer, expert): `lax.scan` slices `w_up_q8` [L, E, d,
    F] -> [E, d, F] and `w_up_s` [L, E] -> [E] per layer, exactly the
    storage `apply_moe` detects. Router/shared/dense weights stay bf16 —
    the mixed-precision deployment `core.cost_model.Precision` prices.
    Modes as in `kernels.moe_gmm.quantize_moe_experts`."""
    from repro.kernels.moe_gmm.quant import (QUANT_SUFFIX, SCALE_SUFFIX,
                                             fake_quant_fp8, quantize_int8)
    if mode not in ("int8", "fp8"):
        raise ValueError(f"unknown quantization mode {mode!r}")
    moe = params.get("blocks", {}).get("moe")
    if not isinstance(moe, dict):
        raise ValueError("params tree has no stacked blocks/moe dict "
                         "(per-layer trees: quantize each layer's dict "
                         "with kernels.moe_gmm.quantize_moe_experts)")
    names = [k for k in ("w_gate", "w_up", "w_down") if k in moe]
    if not names:
        raise ValueError("blocks/moe holds no routed expert tensors")
    new = dict(moe)
    for k in names:
        w = moe[k]
        if mode == "fp8":
            new[k] = fake_quant_fp8(w)
            continue
        lyr, e = w.shape[:2]
        q, s = quantize_int8(w.reshape((lyr * e,) + w.shape[2:]),
                             quantile=quantile)
        new[k + QUANT_SUFFIX] = q.reshape(w.shape)
        new[k + SCALE_SUFFIX] = s.reshape(lyr, e)
        del new[k]
    out = dict(params)
    out["blocks"] = dict(params["blocks"])
    out["blocks"]["moe"] = new
    return out


_EP_CACHE = {}


def _ep_apply(cfg, mesh):
    from repro.distributed.expert_parallel import make_expert_parallel_moe
    key = (cfg.name, tuple(sorted(dict(mesh.shape).items())))
    if key not in _EP_CACHE:
        _EP_CACHE[key] = make_expert_parallel_moe(cfg, mesh)
    return _EP_CACHE[key]


def apply_moe(cfg, p, x2d, *, capacity_policy: str = "train",
              packed: bool = False, kernel_backend: str | None = None):
    """x2d: [T,d] -> (y [T,d], aux dict with routing telemetry).

    packed=True takes the union-packed verification path: below
    saturation the activated experts are compacted into the leading
    `packed_expert_cap(cfg, T)` slots, so weight gathers, the dispatch
    buffer and the FFN matmuls all scale with the (bucketed) union U
    rather than E.  At saturation (`experts_in_place`: U_pad == E) the
    pass takes the dense dispatch by expert id and reads all E stacked
    experts in place, since a gather of all E only copies them.  With
    kernel_backend=None the packed FFN runs the same inline einsums as the
    dense path — identical contraction structure and dtype promotion, so
    the outputs are bit-identical and rejection sampling sees no numerics
    drift.  kernel_backend="pallas"/"interpret"/"ref" keeps the union
    gather at any U_pad and routes the packed FFN through
    `kernels.moe_gmm.moe_gmm_fused` instead (allclose, not bitwise).
    The packed path is the single-host serving hot path; the GSPMD
    dispatch-shard constraints and the ep-a2a path stay dense.

    Quantized expert storage (docs/quantization.md): when `p` holds
    int8-packed experts (`w_up_q8` + `w_up_s` per-expert scales, from
    `kernels.moe_gmm.quantize_moe_experts` — router/shared/dense weights
    stay bf16), the packed union-gather gathers the QUANTIZED tensors and
    their scales, so only 1 byte/param of expert weights streams; with a
    kernel_backend the dequant fuses into `moe_gmm_fused_quant`'s tiles,
    inline the gathered [U_pad]-sized slice dequantizes in-register.  The
    dense/ep paths dequantize up front (correct, not byte-lean — serving
    uses the packed path)."""
    from repro.distributed.sharding import _CONTEXT_MESH, constrain, opt
    t, d = x2d.shape
    quant = "w_up_q8" in p
    if quant and not packed:
        # non-packed consumers (training-style dispatch, ep-a2a) see a
        # dequantized view; only the packed serving path earns the bytes
        from repro.kernels.moe_gmm import dequantize_int8
        p = dict(p)
        for name in ("w_gate", "w_up", "w_down"):
            if name + "_q8" in p:
                p[name] = dequantize_int8(p.pop(name + "_q8"),
                                          p.pop(name + "_s"))
    if opt("ep-a2a") and capacity_policy != "exact":
        # §Perf/beyond-paper: explicit all-to-all expert parallelism
        mesh = _CONTEXT_MESH[0]
        if mesh is not None:
            from repro.distributed.sharding import axis_size, data_axes
            n_data = axis_size(mesh, data_axes(mesh))
            if cfg.num_experts % n_data == 0 and t % n_data == 0:
                y, aux = _ep_apply(cfg, mesh)(
                    {k: p[k] for k in p}, x2d)
                # the gathered routing decision [T,k] feeds the same
                # union/per-row/per-shard accounting as the dense path —
                # summing the per-source-shard counts would double-count
                # experts shared across token shards, so the union is
                # recomputed from the global ids and the raw per-source
                # counts stay visible under their own key
                aux = dict(aux,
                           unique_experts=unique_expert_count(
                               cfg, aux["expert_idx"]),
                           unique_experts_src=aux["unique_experts"],
                           dropped=jnp.sum(aux["dropped"]))
                return y, aux
    k, e = cfg.experts_per_token, cfg.num_experts
    c = _capacity(cfg, t, capacity_policy)

    weights, idx, probs = route(cfg, p, x2d)

    # --- slot assignment: position of each (token, choice) inside its expert
    flat_e = idx.reshape(-1)                                  # [T*k]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)       # [T*k,E]
    pos = jnp.cumsum(onehot, axis=0) * onehot                 # rank within expert
    flat_p = jnp.sum(pos, axis=-1) - 1                        # [T*k], 0-based
    keep = flat_p < c
    flat_p = jnp.where(keep, flat_p, c)  # overflow rows scatter to a spill slot

    x_rep = jnp.repeat(x2d, k, axis=0)                        # [T*k,d]
    if packed and (kernel_backend is not None
                   or not experts_in_place(cfg, p, t)):
        # --- union compaction: map the activated experts onto the leading
        # U_pad packed slots (active experts first, ascending id — a
        # deterministic, trace-stable permutation).  Every routed expert
        # is active, so every (token, choice) lands in a slot < U_pad.
        u_cap = packed_expert_cap(cfg, t)
        hits = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)   # [E]
        active = (hits > 0).astype(jnp.int32)
        perm = jnp.argsort(1 - active, stable=True)           # [E]
        expert_ids = perm[:u_cap]                             # [U_pad]
        slot_of = (jnp.full((e,), u_cap, jnp.int32)
                   .at[expert_ids].set(jnp.arange(u_cap, dtype=jnp.int32)))
        flat_u = slot_of[flat_e]                              # [T*k] < U_pad

        # --- packed dispatch: [U_pad, C(+spill), d]
        disp = jnp.zeros((u_cap, c + 1, d), x2d.dtype)
        disp = disp.at[flat_u, flat_p].set(x_rep)[:, :c]

        # --- gather only the union's weights (the U-not-E byte stream);
        # quantized storage gathers int8 tensors + [U_pad] scales, so the
        # gather itself moves 1 byte/param
        if quant:
            wu_q = jnp.take(p["w_up_q8"], expert_ids, axis=0)
            wd_q = jnp.take(p["w_down_q8"], expert_ids, axis=0)
            su_g = jnp.take(p["w_up_s"], expert_ids, axis=0)
            sd_g = jnp.take(p["w_down_s"], expert_ids, axis=0)
            swiglu = "w_gate_q8" in p and cfg.activation == "swiglu"
            wg_q = (jnp.take(p["w_gate_q8"], expert_ids, axis=0)
                    if swiglu else None)
            sg_g = (jnp.take(p["w_gate_s"], expert_ids, axis=0)
                    if swiglu else None)
            if kernel_backend is not None:
                from repro.kernels.moe_gmm import moe_gmm_fused_quant
                counts = jnp.minimum(hits[expert_ids], c)
                out = moe_gmm_fused_quant(
                    disp, wg_q, wu_q, wd_q, sg_g, su_g, sd_g, counts,
                    activation="swiglu" if swiglu else "gelu",
                    backend=kernel_backend)
            else:
                # in-register dequant of the gathered [U_pad] slice, then
                # the same contractions as the bf16 packed path (matches
                # the kernel's oracle `moe_gmm_fused_quant_ref`)
                from repro.kernels.moe_gmm import dequantize_int8
                wu_g = dequantize_int8(wu_q, su_g)
                wd_g = dequantize_int8(wd_q, sd_g)
                if swiglu:
                    wg_g = dequantize_int8(wg_q, sg_g)
                    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", disp, wg_g))
                    h = h * jnp.einsum("ecd,edf->ecf", disp, wu_g)
                else:
                    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", disp, wu_g))
                out = jnp.einsum("ecf,efd->ecd", h, wd_g)     # [U_pad,C,d]
            pad = jnp.zeros((u_cap, 1, d), out.dtype)
            out = jnp.concatenate([out, pad], axis=1)
            y_rep = out[flat_u, jnp.where(keep, flat_p, c)]   # [T*k,d]
            w_flat = (weights.reshape(-1) * keep).astype(out.dtype)
            y = jnp.sum((y_rep * w_flat[:, None]).reshape(t, k, d), axis=1)
            if cfg.num_shared_experts:
                y = y + apply_mlp(cfg, p["shared"], x2d)
            aux = {
                "lb_loss": load_balance_loss(cfg, probs, idx),
                "expert_idx": idx,
                "unique_experts": unique_expert_count(cfg, idx),
                "dropped": jnp.sum(~keep),
            }
            return y, aux
        wu_g = jnp.take(p["w_up"], expert_ids, axis=0)        # [U_pad,d,F]
        wd_g = jnp.take(p["w_down"], expert_ids, axis=0)      # [U_pad,F,d]
        swiglu = "w_gate" in p and cfg.activation == "swiglu"
        wg_g = (jnp.take(p["w_gate"], expert_ids, axis=0) if swiglu
                else None)
        if kernel_backend is not None:
            from repro.kernels.moe_gmm import moe_gmm_fused
            counts = jnp.minimum(hits[expert_ids], c)
            out = moe_gmm_fused(disp, wg_g, wu_g, wd_g, counts,
                                activation="swiglu" if swiglu else "gelu",
                                backend=kernel_backend)
        else:
            # same contractions/dtypes as the dense branch -> bit-identical
            if swiglu:
                h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", disp, wg_g))
                h = h * jnp.einsum("ecd,edf->ecf", disp, wu_g)
            else:
                h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", disp, wu_g))
            out = jnp.einsum("ecf,efd->ecd", h, wd_g)         # [U_pad,C,d]

        pad = jnp.zeros((u_cap, 1, d), out.dtype)
        out = jnp.concatenate([out, pad], axis=1)
        y_rep = out[flat_u, jnp.where(keep, flat_p, c)]       # [T*k,d]
    else:
        # --- dispatch: scatter tokens into [E, C(+spill), d]; the packed
        # path at saturation lands here too, so the einsums below read the
        # stacked weights in place rather than a gathered copy of all E
        disp = jnp.zeros((e, c + 1, d), x2d.dtype)
        disp = disp.at[flat_e, flat_p].set(x_rep)
        disp = disp[:, :c]                                    # drop spill slot
        if opt("dispatch-shard"):
            # §Perf: pin the dispatch buffer (experts over 'data') so GSPMD
            # does not involuntarily replicate it through the scatter
            disp = constrain(disp, "data", None, None)

        # --- expert FFN (stacked einsum; FLOPs = E*C*d*F per matmul)
        if "w_gate" in p and cfg.activation == "swiglu":
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", disp, p["w_gate"]))
            h = h * jnp.einsum("ecd,edf->ecf", disp, p["w_up"])
        else:
            h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", disp, p["w_up"]))
        if opt("dispatch-shard"):
            h = constrain(h, "data", None, "model")
        out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])      # [E,C,d]
        if opt("dispatch-shard"):
            out = constrain(out, "data", None, None)

        # --- combine: gather each slot's output back to its token
        pad = jnp.zeros((e, 1, d), out.dtype)
        out = jnp.concatenate([out, pad], axis=1)             # spill reads 0
        y_rep = out[flat_e, jnp.where(keep, flat_p, c)]       # [T*k,d]
    w_flat = (weights.reshape(-1) * keep).astype(out.dtype)
    y = jnp.sum((y_rep * w_flat[:, None]).reshape(t, k, d), axis=1)

    if cfg.num_shared_experts:
        y = y + apply_mlp(cfg, p["shared"], x2d)

    aux = {
        "lb_loss": load_balance_loss(cfg, probs, idx),
        "expert_idx": idx,
        "unique_experts": unique_expert_count(cfg, idx),
        "dropped": jnp.sum(~keep),
    }
    return y, aux
