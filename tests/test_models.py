"""Per-architecture smoke tests (deliverable f) + decode/rollback
equivalence — the correctness bedrock for speculative verification."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, ASSIGNED_ARCHS, get_config
from repro.models import transformer as T
from repro.training import make_train_step


def _enc_out(cfg, b=1):
    if cfg.is_encoder_decoder:
        return jnp.ones((b, cfg.encoder_len, cfg.encoder_d_model),
                        jnp.float32) * 0.1
    return None


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_and_train_step(arch, key):
    """Reduced variant: one forward + one train step; shapes + no NaNs."""
    cfg = get_config(arch).reduced()
    assert cfg.num_layers <= 3 and cfg.d_model <= 256
    if cfg.is_moe:
        assert cfg.num_experts <= 4
    params = T.init_params(cfg, key)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    logits, aux = T.train_forward(cfg, params, toks, enc_out=_enc_out(cfg, 2))
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())

    init_state, step = make_train_step(cfg)
    state = init_state(key)
    batch = {"tokens": toks, "labels": toks,
             "mask": jnp.ones((2, 16), jnp.float32)}
    if cfg.is_encoder_decoder:
        batch["enc_out"] = _enc_out(cfg, 2)
    if cfg.vision_stub:
        batch["embeds"] = jax.random.normal(key, (2, 16, cfg.d_model))
        batch["rope_pos"] = jnp.broadcast_to(
            jnp.arange(16, dtype=jnp.int32), (3, 2, 16))
        batch.pop("tokens")
        if cfg.vision_stub:
            batch_tokens = None
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("arch", [
    "mixtral-8x7b",          # MoE
    "kimi-k2-1t-a32b",       # MoE, sigmoid router, shared expert
    "deepseek-v2-236b",      # MLA + MoE
    "rwkv6-3b",              # SSM state rollback
    "recurrentgemma-9b",     # hybrid pattern
    "whisper-large-v3",      # enc-dec
    "chatglm3-6b",           # dense GQA + 2d rope
    "qwen2-vl-7b",           # VLM / M-RoPE
    "deepseek-v2-lite",      # dense layer 0, YaRN MLA, top-k unrenormalised
])
def test_decode_matches_full_forward_and_rollback(arch, key):
    cfg = get_config(arch).reduced()
    params = T.init_params(cfg, key)
    enc = _enc_out(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 15), 0,
                              cfg.vocab_size)
    full, _ = T.train_forward(cfg, params, toks, moe_exact=True, enc_out=enc)
    cache = T.init_cache(cfg, 1, 64)
    _, cache, _ = T.prefill(cfg, params, toks[:, :12], cache, enc_out=enc)
    lo, cache2, _, staged = T.decode_step(cfg, params, cache, toks[:, 12:15])
    np.testing.assert_allclose(np.asarray(full[:, 12:15]), np.asarray(lo),
                               atol=2e-4, rtol=2e-3)
    # reject 2 of 3 -> rollback -> re-verify must still match
    cache3 = T.rollback_cache(cfg, cache2, staged, 1, 12)
    assert int(cache3["length"]) == 13
    lo2, _, _, _ = T.decode_step(cfg, params, cache3, toks[:, 13:15])
    np.testing.assert_allclose(np.asarray(full[:, 13:15]), np.asarray(lo2),
                               atol=2e-4, rtol=2e-3)


def test_sliding_window_ring_cache_matches_windowed_forward(key):
    """long_500k variant: ring cache (window + pad) must reproduce the
    windowed full-sequence forward."""
    cfg = dataclasses.replace(get_config("stablelm-1.6b").reduced(),
                              num_layers=2)
    params = T.init_params(cfg, key)
    win = 8
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 30), 0,
                              cfg.vocab_size)
    full, _ = T.train_forward(cfg, params, toks, window=win)
    cache = T.init_cache(cfg, 1, 64, window=win)
    assert cache["k"].shape[2] == win + 2 * T.SPEC_PAD  # ring, not full len
    _, cache, _ = T.prefill(cfg, params, toks[:, :27], cache, window=win)
    lo, _, _, _ = T.decode_step(cfg, params, cache, toks[:, 27:30],
                                window=win)
    np.testing.assert_allclose(np.asarray(full[:, 27:30]), np.asarray(lo),
                               atol=2e-4, rtol=2e-3)


def test_moe_unique_expert_telemetry(tiny_moe, key):
    cfg, params = tiny_moe
    cache = T.init_cache(cfg, 1, 64)
    toks = jax.random.randint(key, (1, 8), 0, cfg.vocab_size)
    _, cache, aux = T.prefill(cfg, params, toks, cache)
    _, _, aux, _ = T.decode_step(cfg, params, cache, toks[:, :4])
    u = np.asarray(aux["unique_experts"])
    assert u.shape == (cfg.num_layers,)
    assert (u >= cfg.experts_per_token).all()
    assert (u <= cfg.num_experts).all()


def test_reduced_keeps_the_configs_mechanisms():
    """The CPU-sized DeepSeek-V2-Lite keeps a dense layer before an MoE
    layer, no query LoRA, YaRN and un-renormalised top-k; a config with a
    query LoRA keeps one."""
    cfg = get_config("deepseek-v2-lite").reduced()
    assert cfg.layer_kinds() == ("D", "A")
    assert cfg.q_lora_rank == 0 and cfg.use_mla
    assert cfg.yarn_factor == 40.0 and cfg.yarn_mscale_all_dim == 0.707
    assert cfg.norm_topk is False and cfg.num_shared_experts == 1
    params = jax.eval_shape(functools.partial(T.init_params, cfg),
                            jax.random.PRNGKey(0))
    assert "ffn" in params["dense_blocks"] and "moe" in params["blocks"]
    assert "w_q" in params["blocks"]["attn"]
    assert get_config("deepseek-v2-236b").reduced().q_lora_rank == 64


def test_deepseek_v2_lite_counts_its_published_total():
    """15.7 B parameters (the model card), within 2%: the dense layer is
    priced as dense, the 26 MoE layers with 64 experts and 2 shared."""
    cfg = get_config("deepseek-v2-lite")
    assert cfg.param_count() == pytest.approx(15.7e9, rel=0.02)
    dense = cfg._attn_params() + cfg._ffn_params(cfg.d_ff)
    moe = (cfg._attn_params() + 66 * cfg._ffn_params(cfg.moe_d_ff)
           + cfg.d_model * cfg.num_experts)
    assert cfg.param_count() == dense + 26 * moe + 2 * 102400 * 2048


def test_yarn_frequencies_and_score_scale():
    """DeepSeek-V2's YaRN at its published settings (rope dim 64, factor
    40 over 4096 positions, beta 32 and 1, mscale 0.707): the frequencies
    from the published formula, written out here, and the MLA score scale
    1/sqrt(192) * (0.1 * 0.707 * ln 40 + 1)^2."""
    import math
    from repro.models import mla as mla_mod
    from repro.models import rope as rope_mod
    dim, base, factor, orig = 64, 10000.0, 40.0, 4096

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    low, high = math.floor(corr_dim(32)), math.ceil(corr_dim(1))
    assert (low, high) == (10, 23)
    want = []
    for i in range(dim // 2):
        extra = base ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extra / factor * ramp + extra * (1 - ramp))
    got = rope_mod.yarn_inv_freq(dim, base, factor, orig, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[10] == pytest.approx(base ** (-20 / 64), rel=1e-6)
    assert got[23] == pytest.approx(base ** (-46 / 64) / 40, rel=1e-6)
    cfg = get_config("deepseek-v2-lite")
    assert (mla_mod.softmax_scale(cfg) * math.sqrt(192)
            == pytest.approx(1.5896, abs=1e-4))
    # mscale / mscale_all_dim = 1: cos and sin are not rescaled here
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 64))
    pos = jnp.arange(5)[None]
    yarn = rope_mod.apply_rope_scaled(cfg, x, pos)
    plain = rope_mod.apply_rope(x, pos, inv_freq=jnp.asarray(got))
    np.testing.assert_allclose(np.asarray(yarn), np.asarray(plain),
                               rtol=1e-6)
    half = dataclasses.replace(cfg, yarn_mscale=2 * 0.707)
    ratio = (rope_mod.yarn_mscale(40.0, 1.414)
             / rope_mod.yarn_mscale(40.0, 0.707))
    np.testing.assert_allclose(
        np.asarray(rope_mod.apply_rope_scaled(half, x, pos)),
        ratio * np.asarray(plain), rtol=1e-5)


@pytest.mark.parametrize("norm_topk", [True, False])
def test_route_renormalises_only_where_configured(norm_topk):
    from repro.models import moe as moe_mod
    cfg = dataclasses.replace(get_config("deepseek-v2-lite").reduced(),
                              norm_topk=norm_topk)
    kr, kx = jax.random.split(jax.random.PRNGKey(3))
    p = {"router": jax.random.normal(kr, (cfg.d_model, cfg.num_experts))
         / np.sqrt(cfg.d_model)}
    x = jax.random.normal(kx, (5, cfg.d_model))
    weights, idx, probs = moe_mod.route(cfg, p, x)
    raw = jax.nn.softmax(x @ p["router"], axis=-1)
    top = np.take_along_axis(np.asarray(raw), np.asarray(idx), axis=-1)
    np.testing.assert_allclose(np.asarray(probs), np.asarray(raw),
                               rtol=1e-5)
    want = top / top.sum(-1, keepdims=True) if norm_topk else top
    np.testing.assert_allclose(np.asarray(weights), want, rtol=1e-5)
    assert (top.sum(-1) < 1.0).all()


#: sha256 of the jaxprs of the reduced OLMoE's served programs, as they
#: were before `first_k_dense`, `norm_topk` and the YaRN fields existed;
#: a change to what OLMoE's served pass computes changes them
OLMOE_PROGRAMS = {
    "decode_1": ("fa64285318955281fbca1fe64b6d3108"
                 "bd707dd53e012afbf3d17e06a2007564"),
    "decode_8": ("fd70b721b5dc5050ab4c366e101ef6f5"
                 "8ddce5e0a20f5ae200a6271cbe1b1ed5"),
    "prefill": ("a5773d951e29bfe852505cfb3857ef66"
                "89c5fa4cf8c60560899770d7d15b819a"),
}


def test_olmoe_programs_unchanged_by_the_new_fields_defaults():
    """The served decode step (packed MoE, per-row cache, 1- and 8-token
    spans) and the blocking prefill of an OLMoE-shaped config trace to the
    same jaxprs as before the dense prefix, `norm_topk` and YaRN."""
    import hashlib
    cfg = get_config("olmoe-1b-7b").reduced()
    assert (cfg.first_k_dense, cfg.norm_topk, cfg.yarn_factor) == (0, True, 0)
    params = jax.eval_shape(functools.partial(T.init_params, cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 2, 64, per_row=True))
    got = {}
    for t in (1, 8):
        toks = jax.ShapeDtypeStruct((2, t), jnp.int32)
        mask = jax.ShapeDtypeStruct((2, t), jnp.bool_)
        got[f"decode_{t}"] = jax.make_jaxpr(
            lambda p, c, tk, m: T.decode_step(cfg, p, c, tk, token_mask=m,
                                              moe_packed=True))(
            params, cache, toks, mask)
    toks = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    got["prefill"] = jax.make_jaxpr(
        lambda p, tk, c: T.prefill(cfg, p, tk, c))(params, toks, cache)
    assert {k: hashlib.sha256(str(v).encode()).hexdigest()
            for k, v in got.items()} == OLMOE_PROGRAMS


def test_param_counts_sane():
    cfg = get_config("kimi-k2-1t-a32b")
    total = cfg.param_count()
    active = cfg.active_param_count()
    assert 0.8e12 < total < 1.4e12          # ~1T
    assert 20e9 < active < 45e9             # ~32B active
    d2 = get_config("deepseek-v2-236b")
    assert 180e9 < d2.param_count() < 300e9


def test_vlm_mrope_positions(key):
    cfg = get_config("qwen2-vl-7b").reduced()
    params = T.init_params(cfg, key)
    toks = jax.random.randint(key, (1, 12), 0, cfg.vocab_size)
    pos3 = jnp.broadcast_to(jnp.arange(12, dtype=jnp.int32), (3, 1, 12))
    lo_a, _ = T.train_forward(cfg, params, toks, rope_pos=pos3)
    lo_b, _ = T.train_forward(cfg, params, toks)
    # text-only: 3-D ids equal per axis == 1-D path
    np.testing.assert_allclose(np.asarray(lo_a), np.asarray(lo_b),
                               atol=1e-5)
    # genuinely different 2-D layout must change the logits
    pos_img = pos3.at[1].set(pos3[1] // 2).at[2].set(pos3[2] % 3)
    lo_c, _ = T.train_forward(cfg, params, toks, rope_pos=pos_img)
    assert float(jnp.abs(lo_c - lo_a).max()) > 1e-4


# ===================================================================== #
# Union-packed MoE dispatch (docs/kernels.md)
# ===================================================================== #

def _gather_operand_shapes(jaxpr):
    """Operand shapes of every gather in `jaxpr` and its sub-jaxprs."""
    shapes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            shapes.append(tuple(eqn.invars[0].aval.shape))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    shapes += _gather_operand_shapes(sub)
    return shapes


@pytest.mark.parametrize("t", [1, 2, 3, 8, 33])
def test_packed_apply_moe_bit_identical(tiny_moe, t):
    """The packed path's inlined einsums use the dense path's exact
    contraction structure and dtypes, so its output is bitwise equal —
    across token counts spanning U=1-shaped unions to full saturation.
    Below saturation it gathers the U_pad union slots of each expert
    stack; at saturation (U_pad == E) it reads the stacks in place, with
    no gather of an [E, d, F] stack."""
    from repro.models import moe
    cfg, _ = tiny_moe
    p = moe.init_moe(cfg, jax.random.PRNGKey(1), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(t), (t, cfg.d_model),
                          jnp.float32)
    yd, auxd = moe.apply_moe(cfg, p, x, capacity_policy="exact")
    yp, auxp = moe.apply_moe(cfg, p, x, capacity_policy="exact",
                             packed=True)
    assert bool(jnp.all(yd == yp)), f"packed diverged at T={t}"
    np.testing.assert_array_equal(np.asarray(auxd["unique_experts"]),
                                  np.asarray(auxp["unique_experts"]))
    gathered = _gather_operand_shapes(jax.make_jaxpr(
        lambda p, x: moe.apply_moe(cfg, p, x, capacity_policy="exact",
                                   packed=True))(p, x).jaxpr)
    stacks = {p[n].shape for n in ("w_gate", "w_up", "w_down") if n in p}
    in_place = moe.experts_in_place(cfg, p, t)
    assert in_place == (moe.packed_expert_cap(cfg, t) == cfg.num_experts)
    assert in_place == (t >= cfg.num_experts // cfg.experts_per_token)
    if in_place:
        assert not stacks & set(gathered), gathered
    else:
        assert stacks <= set(gathered), gathered


def test_packed_apply_moe_fused_kernel_close(tiny_moe):
    """kernel_backend='interpret' runs the fused Pallas kernel in
    interpret mode over the packed layout — numerically close to the
    inline einsum path (not bit-equal: the kernel accumulates per-tile)."""
    from repro.models import moe
    cfg, _ = tiny_moe
    p = moe.init_moe(cfg, jax.random.PRNGKey(1), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (5, cfg.d_model),
                          jnp.float32)
    y0, _ = moe.apply_moe(cfg, p, x, capacity_policy="exact", packed=True)
    y1, _ = moe.apply_moe(cfg, p, x, capacity_policy="exact", packed=True,
                          kernel_backend="interpret")
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=2e-3)


def test_packed_expert_cap_and_counters(tiny_moe):
    """The packed path's dry-run counters scale with the bucketed union
    cap U_pad, not E: strictly below the dense counters while the union
    is unsaturated, exactly equal once U_pad == E."""
    from repro.models import moe
    cfg, _ = tiny_moe
    e, k = cfg.num_experts, cfg.experts_per_token
    caps = [moe.packed_expert_cap(cfg, t) for t in (1, 2, 4, 64)]
    assert caps[0] == min(2 ** (k - 1).bit_length(), e) or caps[0] <= e
    assert all(c <= e for c in caps)
    assert caps == sorted(caps)            # monotone in T
    assert moe.packed_expert_cap(cfg, 64) == e
    for t in (1, 2, 4, 64):
        cd = moe.moe_pass_counters(cfg, t, capacity_policy="exact")
        cp = moe.moe_pass_counters(cfg, t, capacity_policy="exact",
                                   packed=True)
        assert cp["capacity"] == cd["capacity"]
        if moe.packed_expert_cap(cfg, t) < e:
            assert cp["expert_weight_bytes"] < cd["expert_weight_bytes"]
            assert cp["ffn_flops"] < cd["ffn_flops"]
        else:
            assert cp["expert_weight_bytes"] == cd["expert_weight_bytes"]
            assert cp["ffn_flops"] == cd["ffn_flops"]
