"""DeepSeek-V2's decoder, as `bench/reference/deepseek_v2.py` implements
it: multi-head latent attention (MLA) without query compression, YaRN
rotary positions, leading dense layers (`first_k_dense_replace`) and then
layers of fine-grained routed experts (softmax top-k, greedy, renormalised
only where `norm_topk_prob` holds) beside always-on shared experts. A
configuration file's sizes (`Arch`), the program's `ModelConfig` built
from them and checked against them, the random weights the benchmark
serves, and the operations and bytes the served work needs.

A configuration file holds the published `config.json` keys of its model,
as run: the keys that differ from the source are named in its `reduced`
list, with the published values under `published`.

Weights are made from `--seed`, in the layout the program's
`transformer.init_params` builds (the leading dense layers under
`dense_blocks`, the MoE layers under `blocks`), on the device, in the
type they are served in, in one compiled call. The program splits the
published `kv_a_proj_with_mqa` into the latent (`w_kva`) and rope-key
(`w_kr`) projections and `kv_b_proj` into `w_uk` and `w_uv`; with weights
drawn at random that is a layout of the same architecture. Every matrix
is drawn as float32 N(0, 1) times 1/sqrt(fan-in) and rounded to the
served type; norm scales are 1. Each layer's, and each expert's, leaf has
a key of its own, so the reference regenerates one layer at a time
(`layer_weights`) and gets the very values the program was given.

Counts are taken from the sizes alone. Padded rows, padded span
positions, rejected draft tokens and experts no live token routed to are
not needed work, so they read the same whatever implements the pass.
Attention is counted in the absorbed form the served pass needs: per
earlier position and head, the latent and rope key for the score and the
latent for the weighted sum."""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .moe_decoder import DTYPE_BYTES, EMBED_STD, PROGRAM_RMS_EPS, seed_word


@dataclass(frozen=True)
class Arch:
    """The sizes and semantics of a DeepSeek-V2 decoder."""
    layers: int
    dense_layers: int            # leading layers with a dense FFN
    d_model: int
    heads: int
    kv_lora: int                 # latent width cached per position
    qk_nope: int
    qk_rope: int                 # the shared rope key cached per position
    v_dim: int
    dense_width: int
    experts: int
    top_k: int
    expert_width: int
    shared: int                  # shared experts, one FFN of shared * width
    vocab: int
    rms_eps: float
    rope_theta: float
    yarn_factor: float
    yarn_original_max_pos: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    norm_topk: bool
    routed_scale: float
    tie_embeddings: bool
    dtype: str

    @property
    def dtype_bytes(self) -> int:
        return DTYPE_BYTES[self.dtype]

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers


#: published keys whose other values this architecture does not implement
_FIXED_KEYS = {"hidden_act": "silu", "scoring_func": "softmax",
               "topk_method": "greedy", "n_group": 1, "topk_group": 1,
               "moe_layer_freq": 1, "attention_bias": False,
               "q_lora_rank": None}


def arch_of(conf: dict) -> Arch:
    """The `Arch` of a configuration file; raises ValueError where the
    file states semantics the reference does not implement."""
    bad = {k: conf[k] for k, v in _FIXED_KEYS.items()
           if k in conf and conf[k] != v}
    heads = int(conf["num_attention_heads"])
    if int(conf.get("num_key_value_heads", heads)) != heads:
        bad["num_key_value_heads"] = conf["num_key_value_heads"]
    rs = conf.get("rope_scaling") or {}
    if rs and rs.get("type") != "yarn":
        bad["rope_scaling"] = rs
    if bad:
        raise ValueError(f"{conf['name']}: DeepSeek-V2 semantics the "
                         f"reference does not implement: {bad}")
    return Arch(
        layers=int(conf["num_hidden_layers"]),
        dense_layers=int(conf["first_k_dense_replace"]),
        d_model=int(conf["hidden_size"]),
        heads=heads,
        kv_lora=int(conf["kv_lora_rank"]),
        qk_nope=int(conf["qk_nope_head_dim"]),
        qk_rope=int(conf["qk_rope_head_dim"]),
        v_dim=int(conf["v_head_dim"]),
        dense_width=int(conf["intermediate_size"]),
        experts=int(conf["n_routed_experts"]),
        top_k=int(conf["num_experts_per_tok"]),
        expert_width=int(conf["moe_intermediate_size"]),
        shared=int(conf["n_shared_experts"] or 0),
        vocab=int(conf["vocab_size"]),
        rms_eps=float(conf["rms_norm_eps"]),
        rope_theta=float(conf["rope_theta"]),
        yarn_factor=float(rs.get("factor", 0.0)),
        yarn_original_max_pos=int(rs.get("original_max_position_embeddings",
                                         0)),
        yarn_beta_fast=float(rs.get("beta_fast", 32)),
        yarn_beta_slow=float(rs.get("beta_slow", 1)),
        yarn_mscale=float(rs.get("mscale", 1)),
        yarn_mscale_all_dim=float(rs.get("mscale_all_dim", 0)),
        norm_topk=bool(conf["norm_topk_prob"]),
        routed_scale=float(conf.get("routed_scaling_factor", 1.0)),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=str(conf["torch_dtype"]))


#: the semantics the reference implements; a program config that differs
#: in any of them runs another model than the file states
_PROGRAM_FIXED = {"family": "moe", "norm": "rmsnorm",
                  "activation": "swiglu", "router_score": "softmax",
                  "window": 0, "qk_norm": False, "use_mla": True,
                  "q_lora_rank": 0, "layer_pattern": "",
                  "rope_variant": "standard"}


def program_config(conf: dict):
    """The program's `ModelConfig` for a configuration file: its
    architecture entry (`program_arch`), cut to the file's depth and given
    its dtype (`"program_variant": "reduced"` takes the entry's CPU-sized
    variant, for tests). Raises ValueError where the program's config
    would run other widths or semantics than the file states."""
    from repro.configs import get_config
    a = arch_of(conf)
    base = get_config(conf["program_arch"])
    if conf.get("program_variant") == "reduced":
        base = base.reduced()
    cfg = dataclasses.replace(base, num_layers=a.layers, dtype=a.dtype)
    want = {"d_model": a.d_model, "num_heads": a.heads,
            "num_kv_heads": a.heads, "head_dim": a.qk_nope + a.qk_rope,
            "kv_lora_rank": a.kv_lora, "qk_nope_dim": a.qk_nope,
            "qk_rope_dim": a.qk_rope, "v_head_dim": a.v_dim,
            "d_ff": a.dense_width, "first_k_dense": a.dense_layers,
            "num_experts": a.experts, "experts_per_token": a.top_k,
            "moe_d_ff": a.expert_width, "num_shared_experts": a.shared,
            "norm_topk": a.norm_topk, "rope_theta": a.rope_theta,
            "yarn_factor": a.yarn_factor,
            "vocab_size": a.vocab, "tie_embeddings": a.tie_embeddings,
            **_PROGRAM_FIXED}
    if a.yarn_factor:
        want.update(yarn_original_max_pos=a.yarn_original_max_pos,
                    yarn_beta_fast=a.yarn_beta_fast,
                    yarn_beta_slow=a.yarn_beta_slow,
                    yarn_mscale=a.yarn_mscale,
                    yarn_mscale_all_dim=a.yarn_mscale_all_dim)
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if a.rms_eps != PROGRAM_RMS_EPS:
        bad["rms_norm_eps"] = (PROGRAM_RMS_EPS, a.rms_eps)
    if a.routed_scale != 1.0:
        bad["routed_scaling_factor"] = (1.0, a.routed_scale)
    if bad:
        raise ValueError(f"{conf['name']}: the program's "
                         f"{conf['program_arch']!r} config differs from "
                         f"the file as (program, file): {bad}")
    return cfg


#: a fixed id per leaf, folded into its key
_LEAF = {"embedding": 1, "unembed": 2, "w_q": 3, "w_kva": 4, "w_kr": 5,
         "w_uk": 6, "w_uv": 7, "wo": 8, "router": 9, "w_gate": 10,
         "w_up": 11, "w_down": 12, "shared_gate": 13, "shared_up": 14,
         "shared_down": 15, "ffn_gate": 16, "ffn_up": 17, "ffn_down": 18}
_ATTN = ("w_q", "w_kva", "w_kr", "w_uk", "w_uv", "wo")
_FFN = ("w_gate", "w_up", "w_down")


def _shapes(a: Arch) -> dict:
    d, h, f = a.d_model, a.heads, a.expert_width
    fs, fd = f * a.shared, a.dense_width
    return {"w_q": (d, h, a.qk_nope + a.qk_rope), "w_kva": (d, a.kv_lora),
            "w_kr": (d, a.qk_rope), "w_uk": (a.kv_lora, h, a.qk_nope),
            "w_uv": (a.kv_lora, h, a.v_dim), "wo": (h * a.v_dim, d),
            "router": (d, a.experts),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
            "shared_gate": (d, fs), "shared_up": (d, fs),
            "shared_down": (fs, d),
            "ffn_gate": (d, fd), "ffn_up": (d, fd), "ffn_down": (fd, d)}


def _draw(key, shape, dtype):
    """N(0, 1/fan_in) in float32, rounded to `dtype` (fan-in: shape[0])."""
    w = jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[0])
    return w.astype(dtype)


def _key(word, layer, leaf, expert=0):
    k = jax.random.fold_in(jax.random.PRNGKey(word), layer)
    return jax.random.fold_in(jax.random.fold_in(k, _LEAF[leaf]), expert)


def _dense_layer(a: Arch, word, layer, dtype):
    """A leading layer's attention and dense FFN leaves (`ffn_*`)."""
    sh = _shapes(a)
    return {n: _draw(_key(word, layer, n), sh[n], dtype)
            for n in _ATTN + ("ffn_gate", "ffn_up", "ffn_down")}


def _moe_layer(a: Arch, word, layer, dtype):
    """An MoE layer's attention, router and shared-expert (`shared_*`)
    leaves and its [E, ...] routed expert stacks."""
    sh = _shapes(a)
    out = {n: _draw(_key(word, layer, n), sh[n], dtype)
           for n in _ATTN + ("router", "shared_gate", "shared_up",
                             "shared_down")}
    for n in _FFN:
        out[n] = jax.lax.map(
            lambda e, n=n: _draw(_key(word, layer, n, e), sh[n], dtype),
            jnp.arange(a.experts))
    return out


def _globals(a: Arch, word, dtype):
    emb = (jax.random.normal(_key(word, 0, "embedding"),
                             (a.vocab, a.d_model), jnp.float32)
           * EMBED_STD).astype(dtype)
    out = {"embedding": emb}
    if not a.tie_embeddings:
        out["unembed"] = _draw(_key(word, 0, "unembed"),
                               (a.d_model, a.vocab), dtype)
    return out


def _block(layers: dict, n: int, dtype, a: Arch) -> dict:
    """A stacked group of `n` layers in the program's block layout."""
    ones = jnp.ones((n, a.d_model), dtype)
    attn = {k: layers[k] for k in _ATTN}
    attn["kv_norm"] = jnp.ones((n, a.kv_lora), dtype)
    return {"ln1": {"scale": ones}, "attn": attn, "ln2": {"scale": ones}}


@functools.partial(jax.jit, static_argnums=(0,))
def _program_params(a: Arch, word):
    dtype = jnp.dtype(a.dtype)
    out = {"embed": _globals(a, word, dtype),
           "final_norm": {"scale": jnp.ones((a.d_model,), dtype)}}
    if a.dense_layers:
        dense = jax.lax.map(lambda l: _dense_layer(a, word, l + 1, dtype),
                            jnp.arange(a.dense_layers))
        out["dense_blocks"] = dict(
            _block(dense, a.dense_layers, dtype, a),
            ffn={n: dense["ffn_" + n[2:]] for n in _FFN})
    moe = jax.lax.map(lambda l: _moe_layer(a, word, l + 1, dtype),
                      jnp.arange(a.dense_layers, a.layers))
    experts = {n: moe[n] for n in ("router",) + _FFN}
    if a.shared:
        experts["shared"] = {n: moe["shared_" + n[2:]] for n in _FFN}
    out["blocks"] = dict(_block(moe, a.moe_layers, dtype, a), moe=experts)
    return out


def program_params(a: Arch, seed: int):
    """The program's parameter tree, made on the default device."""
    return jax.block_until_ready(_program_params(a, seed_word(seed)))


@functools.partial(jax.jit, static_argnums=(0,))
def _dense_served(a: Arch, word, layer):
    return _dense_layer(a, word, layer + 1, jnp.dtype(a.dtype))


@functools.partial(jax.jit, static_argnums=(0,))
def _moe_served(a: Arch, word, layer):
    return _moe_layer(a, word, layer + 1, jnp.dtype(a.dtype))


def layer_weights(a: Arch, seed: int, layer: int) -> dict:
    """Layer `layer`'s matrices as the program got them, in the served
    type: w_q, w_kva, w_kr, w_uk, w_uv and wo; then ffn_gate, ffn_up and
    ffn_down in a leading dense layer, or router, shared_gate, shared_up,
    shared_down and [E, ...] w_gate, w_up, w_down in an MoE layer."""
    served = _dense_served if layer < a.dense_layers else _moe_served
    return served(a, seed_word(seed), layer)


@functools.partial(jax.jit, static_argnums=(0,))
def _globals_served(a: Arch, word):
    return _globals(a, word, jnp.dtype(a.dtype))


def global_weights(a: Arch, seed: int) -> dict:
    """The embedding (and untied head) as the program got them."""
    return _globals_served(a, seed_word(seed))


def attn_params(a: Arch) -> int:
    """The MLA projections of one layer: query, latent, rope key, the
    latent's key and value up-projections, and output."""
    h = a.heads
    return (a.d_model * h * (a.qk_nope + a.qk_rope)
            + a.d_model * (a.kv_lora + a.qk_rope)
            + a.kv_lora * h * (a.qk_nope + a.v_dim)
            + h * a.v_dim * a.d_model)


def expert_params(a: Arch) -> int:
    """gate, up and down matrices of one routed expert."""
    return 3 * a.d_model * a.expert_width


def _dense_ffn_params(a: Arch) -> int:
    return 3 * a.d_model * a.dense_width


def _shared_params(a: Arch) -> int:
    return a.shared * expert_params(a)


def dense_params(a: Arch) -> int:
    """Weights every pass reads whatever the routing: attention and its
    norms (ln1, the latent's, ln2) in every layer, the leading layers'
    dense FFN, the router and shared experts of every MoE layer, the final
    norm and the output head (the embedding is only indexed, see
    `pass_bytes`)."""
    per_layer = attn_params(a) + 2 * a.d_model + a.kv_lora
    return (a.layers * per_layer
            + a.dense_layers * _dense_ffn_params(a)
            + a.moe_layers * (a.d_model * a.experts + _shared_params(a))
            + a.d_model + a.d_model * a.vocab)


def _attn_flops_per_position(a: Arch) -> int:
    """Absorbed MLA, one layer: per head, the score over the latent and
    the rope key, and the weighted sum of latents."""
    return 2 * a.heads * (2 * a.kv_lora + a.qk_rope)


def flops_per_token(a: Arch, context: float) -> float:
    """Forward operations of one token at `context` earlier positions:
    2 per multiply-add of every matrix the token meets (attention
    projections, the dense FFN or the router, its top-k experts and the
    shared experts, output head), and the absorbed attention over each
    earlier position and itself in every layer."""
    matmul = (a.layers * attn_params(a)
              + a.dense_layers * _dense_ffn_params(a)
              + a.moe_layers * (a.d_model * a.experts
                                + a.top_k * expert_params(a)
                                + _shared_params(a))
              + a.d_model * a.vocab)
    attn = a.layers * _attn_flops_per_position(a) * (context + 1)
    return 2.0 * matmul + attn


def kv_bytes_per_position(a: Arch) -> int:
    """Latent and rope-key bytes one position holds over all layers."""
    return a.layers * (a.kv_lora + a.qk_rope) * a.dtype_bytes


def pass_bytes(a: Arch, tokens: int, contexts: Sequence[int],
               union_experts_mean: float) -> float:
    """Bytes one pass needs to move: the dense weights once; each MoE
    layer's union of routed experts (`union_experts_mean` is the mean over
    MoE layers) once; the latent cache of every live row's cached
    positions, read, and of the live tokens, written; an embedding row in
    and a row of logits (in the served type) out per live token."""
    b = a.dtype_bytes
    weights = (dense_params(a)
               + a.moe_layers * union_experts_mean * expert_params(a)) * b
    kv = kv_bytes_per_position(a) * (sum(contexts) + tokens)
    rows = tokens * (a.d_model + a.vocab) * b
    return weights + kv + rows


def scope_counts(a: Arch, tokens: int, contexts: Sequence[int],
                 union_experts_mean: float
                 ) -> Dict[str, Tuple[float, float]]:
    """(operations, bytes) one pass needs under each of the program's
    named scopes, counted as `pass_flops` and `pass_bytes` count them:
    `attention` is ln1, the MLA projections and latent norm of every
    layer and the absorbed attention over the live latent cache, which it
    reads and, for the live tokens, writes; `moe_ffn` is the router, the
    union of routed experts and the shared experts of every MoE layer.
    The leading layers' dense FFN (scope `dense_ffn`) is not counted: the
    compiler copies its weights into the chip's on-chip memory by
    asynchronous copies scheduled ahead of the layer, outside the scope,
    so the scope's operations never wait on the weights' HBM bytes. It
    stays `pass_flops`'s and `pass_bytes`'s, with the head, ln2, the final
    norm, and the embedding and logits rows."""
    ctx = sum(contexts) / len(contexts) if contexts else 0.0
    b = a.dtype_bytes
    router = a.d_model * a.experts
    attn_flops = a.layers * tokens * (
        2.0 * attn_params(a) + _attn_flops_per_position(a) * (ctx + 1))
    attn_bytes = (a.layers * (attn_params(a) + a.d_model + a.kv_lora) * b
                  + kv_bytes_per_position(a) * (sum(contexts) + tokens))
    moe_flops = 2.0 * a.moe_layers * tokens * (
        router + a.top_k * expert_params(a) + _shared_params(a))
    moe_bytes = a.moe_layers * (router + _shared_params(a)
                                + union_experts_mean * expert_params(a)) * b
    return {"attention": (attn_flops, attn_bytes),
            "moe_ffn": (moe_flops, moe_bytes)}
