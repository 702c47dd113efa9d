"""Distribution layer: sharding rules (divisibility fallbacks), the HLO
trip-aware analyzer, and a real (subprocess) dry-run on the production
mesh for one arch x shape."""

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed import sharding as sh
from repro.launch.hlo_analysis import analyze_hlo
from repro.models import transformer as T


@pytest.fixture(scope="module")
def mesh44():
    # 4 "devices" arranged logically; on 1 real device jax.make_mesh fails,
    # and an abstract mesh needs no devices at all.
    from repro.launch.mesh import make_abstract_mesh
    return make_abstract_mesh((4, 4), ("data", "model"))


def test_param_rules_divisibility_fallback(mesh44):
    cfg = get_config("whisper-large-v3").reduced()
    shapes = jax.eval_shape(functools.partial(T.init_params, cfg),
                            jax.random.PRNGKey(0))
    specs = sh.param_shardings(cfg, shapes, mesh44)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    for path, ns in flat:
        keys = [str(getattr(p, "key", "")) for p in path]
        leaf = shapes
        for p in path:
            leaf = leaf[getattr(p, "key", getattr(p, "idx", None))]
        # every sharded dim must divide evenly
        for dim, ax in zip(leaf.shape, ns.spec):
            if ax is None:
                continue
            size = 4 if isinstance(ax, str) else 16
            assert dim % size == 0, (keys, leaf.shape, ns.spec)


def test_expert_weights_2d_sharded(mesh44):
    cfg = get_config("mixtral-8x7b")
    shapes = jax.eval_shape(functools.partial(T.init_params, cfg),
                            jax.random.PRNGKey(0))
    specs = sh.param_shardings(cfg, shapes, mesh44)
    wg = specs["blocks"]["moe"]["w_gate"].spec
    assert wg == P(None, "data", None, "model")  # [L, E, d, F]
    wd = specs["blocks"]["moe"]["w_down"].spec
    assert wd == P(None, "data", "model", None)  # [L, E, F, d]
    emb = specs["embed"]["embedding"].spec
    assert emb == P("model", None)


def test_cache_sharding_context_parallel_batch1(mesh44):
    cfg = get_config("stablelm-1.6b")
    cache_shapes = jax.eval_shape(lambda: T.init_cache(cfg, 1, 8192))
    specs = sh.cache_shardings(cfg, cache_shapes, mesh44, batch=1)
    # batch=1: sequence dim must carry 'data' (context parallelism)
    assert specs["k"].spec == P(None, None, "data", "model", None)
    specs_b = sh.cache_shardings(cfg, jax.eval_shape(
        lambda: T.init_cache(cfg, 8, 8192)), mesh44, batch=8)
    assert specs_b["k"].spec[1] in ("data", ("pod", "data"))


def test_hlo_trip_aware_analyzer():
    def f(x, ws):
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y
    x = jnp.zeros((128, 128), jnp.bfloat16)
    ws = jnp.zeros((6, 128, 128), jnp.bfloat16)
    txt = jax.jit(f).lower(x, ws).compile().as_text()
    r = analyze_hlo(txt)
    assert r["flops"] == pytest.approx(6 * 2 * 128 ** 3, rel=0.01)


_EP_PARITY_SCRIPT = r"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs import get_config
from repro.distributed.expert_parallel import make_expert_parallel_moe
from repro.models import moe as moe_mod
from repro.models import transformer as T

assert jax.device_count() == 4, jax.devices()
cfg = get_config("mixtral-8x7b").reduced()          # 4 experts, top-2
mesh = Mesh(np.array(jax.devices()).reshape(4, 1), ("data", "model"))

params = T.init_params(cfg, jax.random.PRNGKey(0))
p = jax.tree.map(lambda x: x[0], params["blocks"]["moe"])   # layer 0
t, d = 32, cfg.d_model
x2d = jax.random.normal(jax.random.PRNGKey(7), (t, d), jnp.float32)

# reference: the dense scatter/gather path at exact capacity (no drops)
y_ref, aux_ref = moe_mod.apply_moe(cfg, p, x2d, capacity_policy="exact")
assert int(aux_ref["dropped"]) == 0

# EP path at the default capacity factor: c_src = T_loc*k*cf // E + 1 = 9
# >= T_loc = 8, so no (source, expert) bucket can overflow -> exact parity
apply_ep = make_expert_parallel_moe(cfg, mesh, capacity_factor=2.0)
y_ep, aux_ep = apply_ep(p, x2d)
np.testing.assert_array_equal(np.asarray(aux_ep["expert_idx"]),
                              np.asarray(aux_ref["expert_idx"]))
assert int(np.sum(np.asarray(aux_ep["dropped"]))) == 0
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                           atol=3e-5, rtol=1e-5)
# lb_loss is pmean-of-local-losses under EP (each device balances its own
# token shard) — an intentional approximation of the full-batch loss
np.testing.assert_allclose(float(aux_ep["lb_loss"]),
                           float(aux_ref["lb_loss"]), rtol=0.05)
# per-source-shard activated counts match the routing decision
idx = np.asarray(aux_ref["expert_idx"])             # [T, k]
src_counts = [len(np.unique(idx[s * 8:(s + 1) * 8])) for s in range(4)]
np.testing.assert_array_equal(np.asarray(aux_ep["unique_experts"]),
                              src_counts)

# the apply_moe wrapper (opt "ep-a2a" + context mesh): the union must be
# the dense path's distinct count, NOT the sum of per-source counts, and
# the raw per-source view stays visible under its own key
from repro.distributed import sharding as sh
sh.set_options(["ep-a2a"], mesh)
try:
    y_wrap, aux_wrap = moe_mod.apply_moe(cfg, p, x2d,
                                         capacity_policy="serve")
finally:
    sh.set_options([], None)
np.testing.assert_allclose(np.asarray(y_wrap), np.asarray(y_ep),
                           atol=3e-5, rtol=1e-5)
assert int(aux_wrap["unique_experts"]) == int(aux_ref["unique_experts"])
np.testing.assert_array_equal(np.asarray(aux_wrap["unique_experts_src"]),
                              src_counts)
assert int(aux_wrap["dropped"]) == 0

# forced-drop case: c_src = 1 -> every (source shard, expert) bucket keeps
# one (token, choice); the dropped counter must account for the overflow
# exactly, computed independently from the routing decision
apply_tiny = make_expert_parallel_moe(cfg, mesh, capacity_factor=1e-6)
y_tiny, aux_tiny = apply_tiny(p, x2d)
expected_drops = 0
for s in range(4):
    vals, counts = np.unique(idx[s * 8:(s + 1) * 8], return_counts=True)
    expected_drops += int(np.sum(np.maximum(counts - 1, 0)))
assert expected_drops > 0
assert int(np.sum(np.asarray(aux_tiny["dropped"]))) == expected_drops
assert np.all(np.isfinite(np.asarray(y_tiny)))
print("EP-PARITY-OK")
"""


def test_expert_parallel_apply_matches_dense_moe(tmp_path):
    """EP numerics parity end-to-end: `make_expert_parallel_moe` on a
    forced 4-device CPU mesh against the dense `moe.apply_moe` scatter
    path — exact routing agreement, allclose outputs when no bucket can
    overflow, and exact dropped-token accounting when one can. Runs in a
    subprocess because the XLA host-device-count flag must precede jax
    initialisation."""
    script = tmp_path / "ep_parity.py"
    script.write_text(_EP_PARITY_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    out = subprocess.run(
        [sys.executable, str(script)], env=env, capture_output=True,
        text=True, timeout=560,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "EP-PARITY-OK" in out.stdout, out.stdout + out.stderr


@pytest.mark.slow
def test_dryrun_subprocess_production_mesh(tmp_path):
    """Real 16x16-mesh lower+compile for one (arch, shape) in a fresh
    process (the XLA device-count flag must precede jax init)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "olmoe-1b-7b" if False else "stablelm-1.6b",
         "--shape", "decode_32k", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=560,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "1/1 combinations compiled" in out.stdout, out.stdout + out.stderr
    rec = json.load(open(os.path.join(
        tmp_path, "stablelm-1.6b_decode_32k_16x16.json")))
    assert rec["ok"] and rec["devices"] == 256
    assert rec["trip_aware"]["flops_per_device"] > 0
