"""A configuration file's sizes (`Arch`), and the program's `ModelConfig`
built from them and checked against them.

A configuration file holds the published `config.json` keys of its model,
as run: the keys that differ from the source are named in its `reduced`
list, with the published values under `published`. The key names are the
source's own (OLMoE's `num_experts`, Mixtral's `num_local_experts`)."""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

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
