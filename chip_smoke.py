"""Chip smoke run: the serving stack's main path on a TPU at published
OLMoE-1B-7B widths, with random bf16 weights made from `--seed`.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: expert-parallel MoE layer

One chip, in one process and in this order:
  1. device       fail unless JAX's first device is a TPU whose kind the
                  cost model's Hardware table lists;
  2. correctness  2 layers: a prompt prefilled as one chunk into a per-row
                  cache, then greedy one-token decode steps on the engine's
                  union-packed pass, against `train_forward` over the whole
                  sequence on float32 copies of the weights;
  3. serving      8 of 16 layers through `repro.launch.serve.serve`
                  (BatchedEngine, wall clock, chunked prefill, Cascade):
                  16 mixed requests must each finish with their tokens.
`--four-chips` runs one MoE layer expert-parallel over a (data=4, model=1)
mesh of chips against the same layer on one chip, and no other phase.

Numbers printed here come from a smoke run, not a benchmark: one pass, with
compiles inside it. The last stdout line is one JSON object,
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}; any
failed check exits non-zero before it is printed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import hardware_for_device_kind  # noqa: E402
from repro.data import make_sample  # noqa: E402
from repro.launch.serve import (MAX_BATCH, MAX_LEN,  # noqa: E402
                                acceptance, mean_granted_k, mixed_requests,
                                serve, use_compile_cache)
from repro.models import transformer as T  # noqa: E402

OLMOE = get_config("olmoe-1b-7b")

#: float32 on both sides: the cached, union-packed pass and the full forward
#: differ only in summation order (~1e-6 relative); a wrong cache slot,
#: position or expert moves a token's logits by order 1
F32_TOL = 1e-4
#: bf16 keeps 8 significant bits, so each rounding of an activation moves
#: it by up to 2^-9 of its size; two layers of such roundings leave the
#: logits a few percent (relative L2, per token) from the float32
#: reference. Where bf16 noise flips a near-tied router choice, one of a
#: token's 8 experts is swapped for its neighbour: that moves the token's
#: MoE output by about half its norm, and its logits by tens of percent.
#: The bound holds on tokens routed alike in both precisions, with room for
#: what reaches them from swapped tokens through attention; swapped tokens
#: are counted, and may not be the majority.
BF16_TOL = 0.1


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip smoke failed: {what}")


def check_device(n_chips: int):
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    require(dev.platform == "tpu",
            f"JAX found platform {dev.platform!r}, not a TPU")
    require(len(devs) >= n_chips,
            f"{n_chips} chips asked for, {len(devs)} found")
    return dev, hardware_for_device_kind(dev.device_kind)


def init_params(cfg, seed: int):
    """Random weights in cfg.dtype, made in one compiled program so no
    float32 copy of a weight stack is materialised on the way."""
    return jax.block_until_ready(jax.jit(T.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed)))


def engine_logits(cfg, params, prompt, n_decode: int, *, packed: bool,
                  feed=None):
    """The engine-shaped pass: `prompt` as one chunked-prefill span into a
    per-row cache, then `n_decode` one-token decode steps on greedy tokens
    (or on `feed`'s tokens past the prompt). Returns the logits [P, V] and
    the MoE layers' router inputs [L, P, d] (float32) of all P positions,
    and the token sequence fed."""
    step = jax.jit(lambda p, c, t, m: T.decode_step(
        cfg, p, c, t, token_mask=m, moe_packed=packed, want_moe_h=True))
    cache = T.init_cache(cfg, 1, MAX_LEN, per_row=True)
    seq = list(prompt)
    logits, hidden = [], []
    toks = jnp.asarray([seq], jnp.int32)
    for i in range(n_decode + 1):
        lo, cache, aux, _ = step(params, cache, toks,
                                 jnp.ones(toks.shape, bool))
        logits.append(np.asarray(lo[0], np.float32))
        hidden.append(np.asarray(aux["moe_h"][:, 0], np.float32))
        if i < n_decode:
            nxt = (int(feed[len(prompt) + i]) if feed is not None
                   else int(np.argmax(logits[-1][-1])))
            seq.append(nxt)
            toks = jnp.asarray([[nxt]], jnp.int32)
    return (np.concatenate(logits), np.concatenate(hidden, axis=1), seq)


def routed_experts(cfg, params, hidden):
    """[L, P, k] sorted expert ids each MoE layer routes each position to,
    from that layer's router inputs (float64 scores on the host)."""
    router = np.asarray(params["blocks"]["moe"]["router"], np.float64)
    scores = np.einsum("lpd,lde->lpe", hidden.astype(np.float64), router)
    top = np.argsort(-scores, axis=-1, kind="stable")
    return np.sort(top[..., :cfg.experts_per_token], axis=-1)


def rel_l2(a, ref):
    return np.linalg.norm(a - ref, axis=-1) / np.linalg.norm(ref, axis=-1)


def check_correctness(cfg, seed: int) -> None:
    n_decode = 4
    params = init_params(cfg, seed)
    rng = np.random.default_rng(seed)
    prompt = make_sample("code", rng, vocab=cfg.vocab_size, prompt_len=31,
                         cont_len=1).prompt
    lo16, h16, seq = engine_logits(cfg, params, prompt, n_decode,
                                   packed=True)
    dense, _, _ = engine_logits(cfg, params, prompt, n_decode, packed=False,
                                feed=seq)
    print(f"correctness: packed vs dense decode logits, max |diff| = "
          f"{float(np.max(np.abs(lo16 - dense)))!r}", flush=True)
    require(np.isfinite(lo16).all(), "non-finite logits")

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    del params
    with jax.default_matmul_precision("highest"):
        lo32, h32, _ = engine_logits(cfg32, p32, prompt, n_decode,
                                     packed=True, feed=seq)
        ref, _ = jax.jit(lambda p, t: T.train_forward(
            cfg32, p, t, moe_exact=True))(p32, jnp.asarray([seq], jnp.int32))
    ref = np.asarray(ref[0], np.float32)
    err32, err16 = rel_l2(lo32, ref), rel_l2(lo16, ref)
    swapped = np.any(routed_experts(cfg, p32, h16)
                     != routed_experts(cfg, p32, h32), axis=(0, 2))
    kept = err16[~swapped]
    print(f"correctness: {cfg.num_layers} layers, {len(prompt)}-token "
          f"prefill chunk + {n_decode} decode steps vs float32 "
          f"train_forward, relative L2 error per position: float32 pass "
          f"max {float(err32.max()):.3g} (bound {F32_TOL}); bf16 pass "
          f"max {float(kept.max()) if kept.size else 0.0:.4f} over "
          f"{kept.size} positions routed alike (bound {BF16_TOL}), "
          f"{int(swapped.sum())} positions with a swapped expert "
          f"(errors {[round(float(e), 4) for e in err16[swapped]]})",
          flush=True)
    require(bool(np.all(err32 <= F32_TOL)),
            f"float32 cached pass {float(err32.max()):.3g} from the "
            f"full forward, bound {F32_TOL}")
    require(bool(np.all(kept <= BF16_TOL)),
            f"bf16 pass {float(kept.max()):.4f} from the float32 "
            f"reference on tokens routed alike, bound {BF16_TOL}")
    require(2 * int(swapped.sum()) <= swapped.size,
            f"{int(swapped.sum())} of {swapped.size} positions routed "
            "differently in bf16 and float32")


def check_serving(cfg, seed: int, hw) -> None:
    max_new = 32
    t0 = time.perf_counter()
    params = init_params(cfg, seed)
    t_init = time.perf_counter() - t0
    reqs = mixed_requests(cfg, 16, seed=seed, max_new=max_new)
    rep = serve(cfg, params, reqs, hw=hw, seed=seed)
    res = rep.results
    require(len(res) == len(reqs),
            f"{len(res)} of {len(reqs)} requests finished")
    for r in res:
        rid = r.telemetry.request_id
        require(len(r.tokens) == max_new,
                f"{rid} emitted {len(r.tokens)} of {max_new} tokens")
        require(all(0 <= t < cfg.vocab_size for t in r.tokens),
                f"{rid} emitted an id outside the vocabulary")
    # the engine raises on a pass with non-finite logits, so reaching here
    # means every pass's logits were finite
    steps = rep.scheduler.engine.telemetry.steps
    spans = sorted({(s.tokens_in_flight + s.padded_tokens) // s.occupancy
                    for s in steps})
    toks = sum(len(r.tokens) for r in res)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"serving (smoke run, not a benchmark): {cfg.name} "
          f"{cfg.num_layers}/{OLMOE.num_layers} layers {cfg.dtype}, "
          f"{len(res)} requests x {max_new} tokens all finished", flush=True)
    print(f"serving: set-up {t_init + rep.first_token_s:.2f} s (init "
          f"{t_init:.2f} s + {rep.first_token_s:.2f} s of compiles and "
          f"passes to the first token); {len(steps)} steps; "
          f"{toks / rep.wall_s:.1f} tok/s over the whole call, "
          f"{rep.scheduler.tokens_per_second():.1f} tok/s engine-clock "
          f"decode; mean granted K {mean_granted_k(res):.3f}; acceptance "
          f"{acceptance(res):.3f}", flush=True)
    passes = sorted(s.t_step for s in steps if not s.prefill_tokens)
    print(f"serving: median pass {passes[len(passes) // 2] * 1e3:.1f} ms "
          f"over {len(passes)} decode-only steps (device pass + logits to "
          f"the host, wall clock)", flush=True)
    print(f"serving: {len(spans)} decode-step shapes compiled (batch "
          f"{MAX_BATCH}, span lengths {spans}); peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)


def check_expert_parallel(cfg, seed: int) -> None:
    """One MoE layer's expert-parallel dispatch (shard_map + all_to_all
    over 'data') against the single-device dense path at exact capacity:
    identical routing, no drops, allclose outputs. float32 under 'highest'
    matmul precision, so the two differ only in accumulation order."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.distributed.expert_parallel import make_expert_parallel_moe
    from repro.models import moe as moe_mod

    devs = jax.devices()
    n, tokens = len(devs), 1024
    mesh = Mesh(np.array(devs).reshape(n, 1), ("data", "model"))
    k_p, k_x = jax.random.split(jax.random.PRNGKey(seed))
    p = jax.jit(moe_mod.init_moe, static_argnums=(0, 2))(cfg, k_p,
                                                         jnp.float32)
    x2d = jax.random.normal(k_x, (tokens, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y_ref, aux_ref = jax.jit(lambda q, x: moe_mod.apply_moe(
            cfg, q, x, capacity_policy="exact"))(p, x2d)
        apply_ep = jax.jit(make_expert_parallel_moe(cfg, mesh))
        y_ep, aux_ep = apply_ep(
            jax.device_put(p, {
                "router": NamedSharding(mesh, P()),
                "w_gate": NamedSharding(mesh, P("data")),
                "w_up": NamedSharding(mesh, P("data")),
                "w_down": NamedSharding(mesh, P("data"))}),
            jax.device_put(x2d, NamedSharding(mesh, P("data"))))
    idx_ref = np.asarray(aux_ref["expert_idx"])
    idx_ep = np.asarray(aux_ep["expert_idx"])
    drops = int(np.sum(np.asarray(aux_ep["dropped"])))
    diff = float(np.max(np.abs(np.asarray(y_ep) - np.asarray(y_ref))))
    print(f"expert-parallel: {cfg.num_experts} experts, "
          f"{cfg.num_experts // n} per chip on {n} chips, {tokens} tokens: "
          f"routing agrees on {int(np.sum(idx_ep == idx_ref))}/"
          f"{idx_ref.size} choices, {drops} dropped, max |diff| {diff!r} "
          f"(max |y| {float(np.max(np.abs(np.asarray(y_ref)))):.4f})",
          flush=True)
    require(int(aux_ref["dropped"]) == 0, "exact-capacity reference dropped")
    require(np.array_equal(idx_ep, idx_ref), "expert-parallel routing "
            "differs from the single-chip layer")
    require(drops == 0, f"{drops} tokens dropped at the default capacity")
    # float32 sums in another order: contraction lengths of 1-2k move a
    # unit-scale output by ~1e-6; a misrouted or lost token moves it by
    # its whole size
    require(np.allclose(np.asarray(y_ep), np.asarray(y_ref),
                        rtol=1e-4, atol=1e-4),
            f"expert-parallel output {diff:.3g} from the single-chip layer")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run the expert-parallel MoE layer on 4 chips "
                         "against one chip, and no other phase")
    args = ap.parse_args()

    dev, hw = check_device(4 if args.four_chips else 1)
    print(f"compile cache: {use_compile_cache(ROOT)}", flush=True)
    if args.four_chips:
        check_expert_parallel(dataclasses.replace(OLMOE, num_layers=1),
                              args.seed)
    else:
        check_correctness(dataclasses.replace(OLMOE, num_layers=2),
                          args.seed)
        check_serving(dataclasses.replace(OLMOE, num_layers=8), args.seed,
                      hw)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
