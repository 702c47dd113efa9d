"""The decoder-only mixture of experts with grouped-query attention that
`bench/reference/moe_decoder.py` implements: a configuration file's sizes
(`Arch`), the program's `ModelConfig` built from them and checked against
them, the random weights the benchmark serves, and the operations and
bytes the served work needs.

A configuration file holds the published `config.json` keys of its model,
as run: the keys that differ from the source are named in its `reduced`
list, with the published values under `published`. The key names are the
source's own (OLMoE's `num_experts`, Mixtral's `num_local_experts`).

Weights are made from `--seed`, in the layout the program's
`transformer.init_params` builds, on the device, in the type they are
served in, in one compiled call. Every matrix is drawn as float32
N(0, 1) times 1/sqrt(fan-in) and rounded to the served type; norm scales
are 1. Each layer's, and each expert's, leaf has a key of its own, so the
reference regenerates one layer at a time (`layer_weights`) and gets the
very values the program was given.

Counts are taken from the sizes alone. Padded rows, padded span
positions, rejected draft tokens and experts no live token routed to are
not needed work, so they read the same whatever implements the pass."""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclass(frozen=True)
class Arch:
    """The sizes and semantics of a decoder-only MoE with grouped-query
    attention, rotary positions, RMSNorm, SwiGLU experts and a softmax
    top-k router."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    expert_width: int
    vocab: int
    rms_eps: float
    rope_theta: float
    norm_topk: bool
    tie_embeddings: bool
    dtype: str

    @property
    def dtype_bytes(self) -> int:
        return DTYPE_BYTES[self.dtype]


def arch_of(conf: dict) -> Arch:
    experts = conf.get("num_experts", conf.get("num_local_experts"))
    heads = int(conf["num_attention_heads"])
    return Arch(
        layers=int(conf["num_hidden_layers"]),
        d_model=int(conf["hidden_size"]),
        heads=heads,
        kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf.get("head_dim")
                     or conf["hidden_size"] // heads),
        experts=int(experts),
        top_k=int(conf["num_experts_per_tok"]),
        expert_width=int(conf["intermediate_size"]),
        vocab=int(conf["vocab_size"]),
        rms_eps=float(conf["rms_norm_eps"]),
        rope_theta=float(conf["rope_theta"]),
        norm_topk=bool(conf.get("norm_topk_prob", True)),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=str(conf["torch_dtype"]))


#: the semantics the reference implements; a program config that differs
#: in any of them runs another model than the file states
_PROGRAM_FIXED = {"family": "moe", "norm": "rmsnorm",
                  "activation": "swiglu", "router_score": "softmax",
                  "num_shared_experts": 0, "window": 0, "qk_norm": False,
                  "use_mla": False, "layer_pattern": "",
                  "rope_variant": "standard"}
#: RMSNorm epsilon of the program's `layers.apply_norm`
PROGRAM_RMS_EPS = 1e-6


def program_config(conf: dict):
    """The program's `ModelConfig` for a configuration file: its
    architecture entry (`program_arch`), cut to the file's depth and given
    its rotary base and dtype (`"program_variant": "reduced"` takes the
    entry's CPU-sized variant, for tests). Raises ValueError where the
    program's config would run other widths or semantics than the file
    states."""
    from repro.configs import get_config
    arch = arch_of(conf)
    base = get_config(conf["program_arch"])
    if conf.get("program_variant") == "reduced":
        base = base.reduced()
    cfg = dataclasses.replace(base,
                              num_layers=arch.layers,
                              rope_theta=arch.rope_theta,
                              dtype=arch.dtype)
    want = {"d_model": arch.d_model, "num_heads": arch.heads,
            "num_kv_heads": arch.kv_heads, "head_dim": arch.head_dim,
            "num_experts": arch.experts,
            "experts_per_token": arch.top_k,
            "moe_d_ff": arch.expert_width, "vocab_size": arch.vocab,
            "tie_embeddings": arch.tie_embeddings, **_PROGRAM_FIXED}
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if arch.rms_eps != PROGRAM_RMS_EPS:
        bad["rms_norm_eps"] = (PROGRAM_RMS_EPS, arch.rms_eps)
    if not arch.norm_topk:
        bad["norm_topk_prob"] = (True, False)
    if conf.get("qk_norm"):
        bad["qk_norm"] = (False, True)
    if bad:
        raise ValueError(f"{conf['name']}: the program's "
                         f"{conf['program_arch']!r} config differs from "
                         f"the file as (program, file): {bad}")
    return cfg


#: a fixed id per leaf, folded into its key
_LEAF = {"embedding": 1, "unembed": 2, "wq": 3, "wk": 4, "wv": 5, "wo": 6,
         "router": 7, "w_gate": 8, "w_up": 9, "w_down": 10}
#: standard deviation of the embedding table (its rows are read, not
#: multiplied, so fan-in does not apply)
EMBED_STD = 1.0


def seed_word(seed: int) -> np.uint32:
    """A 32-bit key word from a seed of any size."""
    return np.uint32(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def _shapes(a: Arch) -> dict:
    d, f = a.d_model, a.expert_width
    return {"wq": (d, a.heads * a.head_dim),
            "wk": (d, a.kv_heads * a.head_dim),
            "wv": (d, a.kv_heads * a.head_dim),
            "wo": (a.heads * a.head_dim, d),
            "router": (d, a.experts),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def _draw(key, shape, dtype):
    """N(0, 1/fan_in) in float32, rounded to `dtype` (fan-in: shape[0])."""
    w = jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[0])
    return w.astype(dtype)


def _key(word, layer, leaf, expert=0):
    k = jax.random.fold_in(jax.random.PRNGKey(word), layer)
    return jax.random.fold_in(jax.random.fold_in(k, _LEAF[leaf]), expert)


def _layer(a: Arch, word, layer, dtype):
    """One layer's dense leaves and its [E, ...] expert stacks."""
    sh = _shapes(a)
    out = {n: _draw(_key(word, layer, n), sh[n], dtype)
           for n in ("wq", "wk", "wv", "wo", "router")}
    for n in ("w_gate", "w_up", "w_down"):
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


@functools.partial(jax.jit, static_argnums=(0,))
def _program_params(a: Arch, word):
    dtype = jnp.dtype(a.dtype)
    layers = jax.lax.map(lambda l: _layer(a, word, l + 1, dtype),
                         jnp.arange(a.layers))
    ones = jnp.ones((a.layers, a.d_model), dtype)
    return {
        "embed": _globals(a, word, dtype),
        "blocks": {
            "ln1": {"scale": ones},
            "attn": {n: layers[n] for n in ("wq", "wk", "wv", "wo")},
            "ln2": {"scale": ones},
            "moe": {n: layers[n]
                    for n in ("router", "w_gate", "w_up", "w_down")},
        },
        "final_norm": {"scale": jnp.ones((a.d_model,), dtype)},
    }


def program_params(a: Arch, seed: int):
    """The program's parameter tree, made on the default device."""
    return jax.block_until_ready(_program_params(a, seed_word(seed)))


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_served(a: Arch, word, layer):
    return _layer(a, word, layer + 1, jnp.dtype(a.dtype))


def layer_weights(a: Arch, seed: int, layer: int) -> dict:
    """Layer `layer`'s matrices as the program got them, in the served
    type: wq, wk, wv, wo, router, and [E, ...] w_gate, w_up, w_down."""
    return _layer_served(a, seed_word(seed), layer)


@functools.partial(jax.jit, static_argnums=(0,))
def _globals_served(a: Arch, word):
    return _globals(a, word, jnp.dtype(a.dtype))


def global_weights(a: Arch, seed: int) -> dict:
    """The embedding (and untied head) as the program got them."""
    return _globals_served(a, seed_word(seed))


def attn_params(a: Arch) -> int:
    """q, k, v and output projections of one layer."""
    return (2 * a.d_model * a.heads * a.head_dim
            + 2 * a.d_model * a.kv_heads * a.head_dim)


def expert_params(a: Arch) -> int:
    """gate, up and down matrices of one expert."""
    return 3 * a.d_model * a.expert_width


def dense_params(a: Arch) -> int:
    """Weights every pass reads whatever the routing: attention, router
    and norms of every layer, the final norm and the output head (the
    embedding is only indexed, see `pass_bytes`)."""
    per_layer = attn_params(a) + a.d_model * a.experts + 2 * a.d_model
    return a.layers * per_layer + a.d_model + a.d_model * a.vocab


def flops_per_token(a: Arch, context: float) -> float:
    """Forward operations of one token at `context` earlier positions:
    2 per multiply-add of every matrix the token meets (attention
    projections, router, its top-k experts, output head), and 4 * heads *
    head_dim per earlier position and layer for the scores and the
    weighted sum of values."""
    matmul = (a.layers * (attn_params(a) + a.d_model * a.experts
                          + a.top_k * expert_params(a))
              + a.d_model * a.vocab)
    attn = 4 * a.layers * a.heads * a.head_dim * (context + 1)
    return 2.0 * matmul + attn


def kv_bytes_per_position(a: Arch) -> int:
    """Key and value bytes one position holds over all layers."""
    return 2 * a.layers * a.kv_heads * a.head_dim * a.dtype_bytes


def pass_bytes(a: Arch, tokens: int, contexts: Sequence[int],
               union_experts_mean: float) -> float:
    """Bytes one pass needs to move: the dense weights once; each layer's
    union of routed experts (`union_experts_mean` is the mean over layers)
    once; the keys and values of every live row's cached positions, read,
    and of the live tokens, written; an embedding row in and a row of
    logits (in the served type) out per live token."""
    b = a.dtype_bytes
    weights = (dense_params(a)
               + a.layers * union_experts_mean * expert_params(a)) * b
    kv = kv_bytes_per_position(a) * (sum(contexts) + tokens)
    rows = tokens * (a.d_model + a.vocab) * b
    return weights + kv + rows


def scope_counts(a: Arch, tokens: int, contexts: Sequence[int],
                 union_experts_mean: float
                 ) -> Dict[str, Tuple[float, float]]:
    """(operations, bytes) one pass needs under each of the program's
    named scopes, counted as `pass_flops` and `pass_bytes` count them:
    `attention` is ln1, the q, k, v and output projections of every layer
    and the scores and weighted values over the live keys and values,
    which it reads and, for the live tokens, writes; `moe_ffn` is the
    router of every layer and the union of its routed experts. The head,
    the other norms, and the embedding and logits rows are `pass_bytes`'s
    alone."""
    ctx = sum(contexts) / len(contexts) if contexts else 0.0
    b = a.dtype_bytes
    router = a.d_model * a.experts
    attn_flops = a.layers * tokens * (
        2.0 * attn_params(a) + 4 * a.heads * a.head_dim * (ctx + 1))
    attn_bytes = (a.layers * (attn_params(a) + a.d_model) * b
                  + kv_bytes_per_position(a) * (sum(contexts) + tokens))
    moe_flops = 2.0 * a.layers * tokens * (router
                                           + a.top_k * expert_params(a))
    moe_bytes = a.layers * (router
                            + union_experts_mean * expert_params(a)) * b
    return {"attention": (attn_flops, attn_bytes),
            "moe_ffn": (moe_flops, moe_bytes)}
