"""Explicit expert-parallel MoE dispatch via shard_map + all_to_all
(§Perf / beyond-paper: the paper's vLLM setting is single-GPU; at pod scale
the GSPMD scatter-based dispatch all-gathers tokens — this module routes
them with one all-to-all each way, the Switch/GShard communication pattern,
expressed jax-natively).

Layout contract (matches distributed/sharding.py):
    tokens  x2d [T, d]        T sharded over 'data' (and 'pod' if present)
    experts                   E sharded over 'data'
    expert weights [E, d, F]  E over 'data', F over 'model'
    router [d, E]             replicated

Inside the per-device block:
    1. route locally (top-k over all E experts)
    2. pack a send buffer [n_data, E_local, C_src, d] (slot assignment via
       local cumsum; per-source-shard quota C_src bounds worst-case skew)
    3. all_to_all over 'data'  ->  [n_data, E_local, C_src, d] recv
    4. grouped expert FFN on the local experts (F sharded over 'model',
       contributions psum'd over 'model')
    5. all_to_all back + weighted combine

Collective volume per layer: 2 x T*k*cf*d bytes spread across the data
axis — versus the baseline's involuntary all-gathers of the full dispatch
buffer."""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import moe as moe_mod


def _local_pack(cfg, x_loc, idx, n_data: int, c_src: int):
    """Build the send buffer on one device.

    x_loc: [T_loc, d]; idx: [T_loc, k] routed expert ids.
    Returns (send, (dst, e_within, slot_c, keep)) where
      send     [n_data, e_loc, c_src, d] — token inputs slotted by
               (destination shard, local expert, arrival rank), spill
               entries already dropped;
      dst      [T_loc*k] destination shard of each (token, choice);
      e_within [T_loc*k] expert index within its shard;
      slot_c   [T_loc*k] capacity-clamped slot (== c_src for spilled);
      keep     [T_loc*k] bool, False where the (token, choice) overflowed
               its per-source quota and was dropped from the send buffer.
    The combine path gathers with (dst, e_within, slot_c) and zeroes
    dropped choices via `keep` — routing weights are applied there, not
    here."""
    t_loc, d = x_loc.shape
    k = cfg.experts_per_token
    e_loc = cfg.num_experts // n_data

    flat_e = idx.reshape(-1)                        # [T_loc*k]
    dst = flat_e // e_loc
    e_within = flat_e % e_loc
    onehot = jax.nn.one_hot(flat_e, cfg.num_experts, dtype=jnp.int32)
    slot = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
    keep = slot < c_src
    slot_c = jnp.where(keep, slot, c_src)           # spill row

    x_rep = jnp.repeat(x_loc, k, axis=0)
    send = jnp.zeros((n_data, e_loc, c_src + 1, d), x_loc.dtype)
    send = send.at[dst, e_within, slot_c].set(x_rep)
    send = send[:, :, :c_src]
    return send, (dst, e_within, slot_c, keep)


def _expert_ffn(cfg, p, xs):
    """xs: [e_loc, C, d]; local expert weights (F already model-sharded)."""
    if "w_gate" in p and cfg.activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, p["w_gate"]))
        h = h * jnp.einsum("ecd,edf->ecf", xs, p["w_up"])
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xs, p["w_up"]))
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def make_expert_parallel_moe(cfg, mesh: Mesh, *, capacity_factor: float = 2.0):
    """Returns apply(p, x2d) with the same semantics as moe.apply_moe
    (minus token-drop differences at quota boundaries)."""
    from .sharding import data_axes
    data_ax = data_axes(mesh)   # 'data' or ('pod','data')
    model_ax = "model"
    sizes = dict(mesh.shape)
    n_data = (sizes[data_ax] if isinstance(data_ax, str)
              else sizes["pod"] * sizes["data"])
    assert cfg.num_experts % n_data == 0

    def per_device(p, x_loc):
        t_loc, d = x_loc.shape
        k, e = cfg.experts_per_token, cfg.num_experts
        c_src = max(int(t_loc * k * capacity_factor) // e + 1, 1)

        weights, idx, probs = moe_mod.route(cfg, p, x_loc)
        send, (dst, e_within, slot_c, keep) = _local_pack(
            cfg, x_loc, idx, n_data, c_src)

        # one all-to-all each way over the data axis
        recv = jax.lax.all_to_all(send, data_ax, split_axis=0,
                                  concat_axis=0, tiled=True)
        # recv: [n_src, e_loc, c_src, d] -> group per expert
        e_loc = e // n_data
        xs = jnp.moveaxis(recv, 0, 1).reshape(e_loc, n_data * c_src, d)

        out = _expert_ffn(cfg, p, xs)                   # [e_loc, C, d]
        out = jax.lax.psum(out, model_ax)               # w_down F-contraction

        back = jnp.moveaxis(out.reshape(e_loc, n_data, c_src, d), 1, 0)
        ret = jax.lax.all_to_all(back, data_ax, split_axis=0,
                                 concat_axis=0, tiled=True)
        # ret: [n_dst, e_loc, c_src, d] == layout of `send`
        pad = jnp.zeros((n_data, e_loc, 1, d), ret.dtype)
        ret = jnp.concatenate([ret, pad], axis=2)
        y_rep = ret[dst, e_within, slot_c]               # [T_loc*k, d]
        w_flat = (weights.reshape(-1) * keep).astype(y_rep.dtype)
        y = jnp.sum((y_rep * w_flat[:, None]).reshape(t_loc, k, d), axis=1)

        if cfg.num_shared_experts:
            from repro.models.layers import apply_mlp
            # shared-expert F dim is model-sharded: partial contributions
            y = y + jax.lax.psum(apply_mlp(cfg, p["shared"], x_loc),
                                 model_ax)

        aux = {
            "lb_loss": jax.lax.pmean(
                moe_mod.load_balance_loss(cfg, probs, idx), data_ax),
            # per-source-shard telemetry (concatenated over data by
            # out_specs); the *global* routing decision is emitted too so
            # batch-aware consumers (per-row attribution, per-expert-shard
            # unions) see the same [T, k] ids the dense path reports
            "unique_experts": moe_mod.unique_expert_count(cfg, idx)[None],
            "dropped": jnp.sum(~keep)[None],
            "expert_idx": idx,
        }
        return y, aux

    p_specs = {
        "router": P(None, None),
        "w_gate": P(data_ax, None, model_ax),
        "w_up": P(data_ax, None, model_ax),
        "w_down": P(data_ax, model_ax, None),
    }
    if cfg.num_shared_experts:
        p_specs["shared"] = {"w_gate": P(None, model_ax),
                             "w_up": P(None, model_ax),
                             "w_down": P(model_ax, None)}

    apply = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(p_specs, P(data_ax, None)),
        out_specs=(P(data_ax, None),
                   {"lb_loss": P(), "unique_experts": P(data_ax),
                    "dropped": P(data_ax), "expert_idx": P(data_ax, None)}),
        check_vma=False)
    return apply
