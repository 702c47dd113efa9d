"""Random weights made from `--seed` by the benchmark, in the layout the
program's `transformer.init_params` builds, on the device, in the type they
are served in, in one compiled call.

Every matrix is drawn as float32 N(0, 1) times 1/sqrt(fan-in) and rounded
to the served type; norm scales are 1. Each layer's, and each expert's,
leaf has a key of its own, so the reference regenerates one layer at a
time (`layer_weights`) and gets the very values the program was given."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .model import Arch

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
