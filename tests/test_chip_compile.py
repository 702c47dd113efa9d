"""Compiles for a described TPU v5e at published widths, with no chip
attached: the fused MoE kernels, the served 8-layer OLMoE-1B-7B and
DeepSeek-V2-Lite decode steps (which must fit one chip's HBM), and the
expert-parallel MoE layer over a 2x2 mesh. What the chip's compiler
would refuse (tile alignment, VMEM limits, a program too large for HBM)
fails here. Nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file."""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.core import TPU_V5E, hardware_for_device_kind
from repro.kernels.moe_gmm import moe_gmm_fused, moe_gmm_fused_quant
from repro.models import moe as moe_mod
from repro.models import transformer as T

OLMOE = get_config("olmoe-1b-7b")
DSV2_LITE = get_config("deepseek-v2-lite")
HBM_BUDGET = 15 * 2 ** 30   # one v5e chip's 16 GiB, less 1 GiB of headroom

# (U packed expert slots, C tokens per slot, d_model, expert width F)
WIDTHS = {"olmoe": (64, 32, 2048, 1024), "mixtral": (8, 32, 4096, 14336)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def test_device_kind_prices_as_v5e(topo):
    assert hardware_for_device_kind(topo.devices[0].device_kind) is TPU_V5E


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_fused_moe_kernel_compiles(one_chip, width, quant):
    u, c, d, f = WIDTHS[width]
    wdt = jnp.int8 if quant else jnp.bfloat16
    x = jax.ShapeDtypeStruct((u, c, d), jnp.bfloat16, sharding=one_chip)
    wg = jax.ShapeDtypeStruct((u, d, f), wdt, sharding=one_chip)
    wd = jax.ShapeDtypeStruct((u, f, d), wdt, sharding=one_chip)
    vec = functools.partial(jax.ShapeDtypeStruct, (u,), sharding=one_chip)
    if quant:
        fn = functools.partial(moe_gmm_fused_quant, backend="pallas")
        args = (x, wg, wg, wd, vec(jnp.float32), vec(jnp.float32),
                vec(jnp.float32), vec(jnp.int32))
    else:
        fn = functools.partial(moe_gmm_fused, backend="pallas")
        args = (x, wg, wg, wd, vec(jnp.int32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _defined_with_shape(hlo_text, shapes):
    """Instructions of the optimized HLO whose result has one of `shapes`
    (bf16), parameters and bitcasts left out: they define no new array."""
    dims = "|".join(",".join(map(str, s)) for s in shapes)
    pat = re.compile(rf"^\s*(?:ROOT )?%\S+ = bf16\[({dims})\]\S* "
                     rf"(?!parameter\(|bitcast\()", re.M)
    return pat.findall(hlo_text)


@pytest.mark.parametrize("t", [1, 4, 32])
def test_served_decode_step_fits_one_chip(one_chip, t):
    """The pass the serving path runs at published OLMoE widths, depth cut
    to 8 layers: batch 8, t-token spans, 2048-token per-row cache,
    union-packed MoE. 8 rows route 64·t >= E (token, choice) pairs, so
    the packed path reads each layer's expert stacks in place: the
    program defines one array per expert matrix of a [64, d, F] stack
    shape (the per-layer slice fused into its einsum), not a gathered
    copy of each, and its scratch stays small."""
    cfg = dataclasses.replace(OLMOE, num_layers=8)
    assert moe_mod.packed_expert_cap(cfg, 8 * t) == cfg.num_experts
    params = _on(one_chip, jax.eval_shape(
        functools.partial(T.init_params, cfg), jax.random.PRNGKey(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: T.init_cache(cfg, 8, 2048, per_row=True)))
    toks = jax.ShapeDtypeStruct((8, t), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((8, t), jnp.bool_, sharding=one_chip)
    step = jax.jit(lambda p, c, tk, m: T.decode_step(
        cfg, p, c, tk, token_mask=m, moe_packed=True))
    compiled = step.lower(params, cache, toks, mask).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BUDGET, total
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    stacks = _defined_with_shape(compiled.as_text(), [(e, d, f), (e, f, d)])
    assert len(stacks) <= 3, stacks
    assert mem.temp_size_in_bytes < 0.3e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("t", [1, 32])
def test_served_deepseek_v2_lite_decode_step_fits_one_chip(one_chip, t):
    """The pass the serving path runs at published DeepSeek-V2-Lite
    widths, depth cut to 8 layers (the dense layer 0 and 7 MoE layers):
    batch 8, t-token spans, a 2048-position latent cache per row,
    union-packed MoE. Its MoE layers read their experts in place, as
    OLMoE's do, and the latent cache is two stacks per layer group."""
    cfg = dataclasses.replace(DSV2_LITE, num_layers=8)
    assert moe_mod.packed_expert_cap(cfg, 8 * t) == cfg.num_experts
    params = _on(one_chip, jax.eval_shape(
        functools.partial(T.init_params, cfg), jax.random.PRNGKey(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: T.init_cache(cfg, 8, 2048, per_row=True)))
    assert cache["ckv"].shape == (7, 8, 2048, 512)
    assert cache["dense_krope"].shape == (1, 8, 2048, 64)
    toks = jax.ShapeDtypeStruct((8, t), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((8, t), jnp.bool_, sharding=one_chip)
    step = jax.jit(lambda p, c, tk, m: T.decode_step(
        cfg, p, c, tk, token_mask=m, moe_packed=True))
    compiled = step.lower(params, cache, toks, mask).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BUDGET, total
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    stacks = _defined_with_shape(compiled.as_text(), [(e, d, f), (e, f, d)])
    assert len(stacks) <= 3, stacks


def test_expert_parallel_moe_compiles_on_four_chips(topo):
    """One OLMoE MoE layer dispatched over a (data=4, model=1) mesh: 16
    experts per chip, tokens exchanged by all-to-all."""
    from repro.distributed.expert_parallel import make_expert_parallel_moe
    cfg = dataclasses.replace(OLMOE, num_layers=1)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    shapes = jax.eval_shape(
        lambda k: moe_mod.init_moe(cfg, k, jnp.float32),
        jax.random.PRNGKey(0))
    p = {name: jax.ShapeDtypeStruct(
        s.shape, s.dtype,
        sharding=NamedSharding(mesh, P() if name == "router" else P("data")))
        for name, s in shapes.items()}
    x = jax.ShapeDtypeStruct((1024, cfg.d_model), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    compiled = jax.jit(make_expert_parallel_moe(cfg, mesh)).lower(
        p, x).compile()
    assert "all-to-all" in compiled.as_text()
