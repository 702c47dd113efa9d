"""Continuous-batching correctness: per-row rollback vs. single-request
rollbacks, BatchedEngine(B=1) bit-identity with the legacy ServingEngine,
batch cost-model reduction to the single-request model, and the scheduler's
admission/retire behaviour."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (CascadeController, StaticKController, TPU_V5E,
                        batch_iteration_time, expected_unique_experts,
                        expected_unique_experts_batch, iteration_time)
from repro.models import transformer as T
from repro.serving import (BatchedEngine, ContinuousBatchingScheduler,
                           NGramDrafter, Request, Scheduler, ServingEngine)


# ===================================================================== #
# Cost model: batch reduces to single-request
# ===================================================================== #

@pytest.mark.parametrize("arch", ["mixtral-8x7b", "stablelm-1.6b"])
def test_batch_iteration_time_b1_equals_iteration_time(arch):
    cfg = get_config(arch)
    for n, ctx, uniq in [(1, 128, None), (4, 1024, None), (9, 4096, 6.0)]:
        a = iteration_time(cfg, TPU_V5E, n, ctx, unique_experts=uniq,
                           affinity=0.3)
        b = batch_iteration_time(cfg, TPU_V5E, [n], [ctx],
                                 unique_experts=uniq, affinity=0.3)
        assert b["t_iter"] == a["t_iter"]
        assert b["per_request"][0]["t_attr"] == a["t_iter"]


def test_batch_attribution_sums_to_total():
    cfg = get_config("mixtral-8x7b")
    r = batch_iteration_time(cfg, TPU_V5E, [4, 2, 9, 1],
                             [100, 2000, 50, 800], affinity=0.2)
    s = sum(p["t_attr"] for p in r["per_request"])
    assert s == pytest.approx(r["t_iter"], rel=1e-12)
    # a request with a longer context owns more bytes (its own KV read)
    long_ctx = r["per_request"][1]["bytes_attr"]
    short_ctx = r["per_request"][2]["bytes_attr"]
    assert long_ctx > 0 and short_ctx > 0


def test_expected_union_grows_sublinearly():
    """The batch-level Fig. 2 effect: the expert union grows with total
    drafted tokens but saturates, so each extra request's marginal expert
    cost shrinks — speculation utility degrades as the batch fills."""
    e, k = 8, 2
    one = expected_unique_experts(e, k, 4)
    batch = expected_unique_experts_batch(e, k, [4, 4, 4, 4])
    assert batch["union"] > one            # more tokens, more experts...
    assert batch["union"] < 4 * one        # ...but far from additive
    m = batch["marginal"]
    assert all(mi < one for mi in m)       # marginal < standalone cost
    assert batch["union"] <= e


def test_empty_rows_cost_nothing():
    cfg = get_config("mixtral-8x7b")
    a = batch_iteration_time(cfg, TPU_V5E, [3, 0], [128, 0])
    b = iteration_time(cfg, TPU_V5E, 3, 128)
    assert a["t_iter"] == b["t_iter"]
    assert a["per_request"][1]["t_attr"] == 0.0


# ===================================================================== #
# Per-row rollback == loop of single-request rollbacks
# ===================================================================== #

def test_per_row_rollback_matches_single_request_loop(tiny_moe):
    cfg, params = tiny_moe
    prompts = [list(range(3, 19)), list(range(7, 31)),
               [5, 6, 7] * 6]
    spans = [[5, 6, 7], [9], [4, 2]]
    accepts = [2, 1, 0]

    # single-request path, one cache per request
    singles = []
    for p, sp, acc in zip(prompts, spans, accepts):
        c = T.init_cache(cfg, 1, 128)
        _, c, _ = T.prefill(cfg, params, jnp.asarray([p], jnp.int32), c)
        lo, c, _, st = T.decode_step(cfg, params, c,
                                     jnp.asarray([sp], jnp.int32))
        singles.append(T.rollback_cache(cfg, c, st, acc, len(p)))

    # batched per-row path
    bc = T.init_cache(cfg, 3, 128, per_row=True)
    for i, p in enumerate(prompts):
        c = T.init_cache(cfg, 1, 128)
        _, c, _ = T.prefill(cfg, params, jnp.asarray([p], jnp.int32), c)
        bc = T.write_cache_row(bc, i, c)
    t_max = max(len(s) for s in spans)
    toks = np.zeros((3, t_max), np.int32)
    mask = np.zeros((3, t_max), bool)
    for i, sp in enumerate(spans):
        toks[i, :len(sp)] = sp
        mask[i, :len(sp)] = True
    lens_before = np.asarray(bc["lengths"])
    _, bc, _, st = T.decode_step(cfg, params, bc, jnp.asarray(toks),
                                 token_mask=jnp.asarray(mask))
    bc = T.rollback_cache(cfg, bc, st, jnp.asarray(accepts),
                          jnp.asarray(lens_before))

    for i, (single, p, acc) in enumerate(zip(singles, prompts, accepts)):
        assert int(bc["lengths"][i]) == len(p) + acc
        assert int(single["length"]) == len(p) + acc
        pos_b = np.asarray(bc["pos"][i])
        pos_s = np.asarray(single["pos"][0])
        np.testing.assert_array_equal(pos_b, pos_s)
        valid = pos_s >= 0
        k_b = np.asarray(bc["k"][:, i])[:, valid]
        k_s = np.asarray(single["k"][:, 0])[:, valid]
        np.testing.assert_allclose(k_b, k_s, atol=3e-5)


# ===================================================================== #
# BatchedEngine(B=1) == legacy ServingEngine, bit for bit
# ===================================================================== #

@pytest.mark.parametrize("temperature", [0.0, 1.0])
@pytest.mark.parametrize("controller_factory", [
    lambda: StaticKController(3),
    lambda: CascadeController(),
])
def test_batched_b1_bit_identical_to_legacy(tiny_moe, temperature,
                                            controller_factory):
    cfg, params = tiny_moe
    prompt = [5, 6, 7, 8, 9] * 8
    leg = ServingEngine(cfg, params, NGramDrafter(), max_len=512,
                        temperature=temperature, clock="model", seed=7)
    bat = BatchedEngine(cfg, params, lambda: NGramDrafter(), max_batch=1,
                        max_len=512, temperature=temperature,
                        clock="model", seed=7)
    r1 = leg.generate(prompt, max_new=32, controller=controller_factory())
    r2 = bat.generate(prompt, max_new=32, controller=controller_factory())
    assert r1.tokens == r2.tokens
    assert len(r1.telemetry.iterations) == len(r2.telemetry.iterations)
    # same virtual clock, so Cascade saw identical attributed times
    assert r1.telemetry.decode_time == r2.telemetry.decode_time


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_joint_policy_b1_bit_identical_to_independent(tiny_moe, temperature):
    """The planner bypass at B=1: BatchedEngine(policy="joint") must emit a
    bit-identical token stream AND identical telemetry to the per-request
    controller path (policy="independent") on fixed seeds — the planner is
    invisible in the paper's single-batch regime."""
    cfg, params = tiny_moe
    prompt = [5, 6, 7, 8, 9] * 8

    def run(policy):
        eng = BatchedEngine(cfg, params, lambda: NGramDrafter(),
                            max_batch=1, max_len=512,
                            temperature=temperature, clock="model",
                            seed=7, policy=policy)
        return eng.generate(prompt, max_new=32,
                            controller=CascadeController()), eng

    r_joint, e_joint = run("joint")
    r_ind, e_ind = run("independent")
    assert r_joint.tokens == r_ind.tokens
    assert r_joint.telemetry.decode_time == r_ind.telemetry.decode_time
    its_j, its_i = r_joint.telemetry.iterations, r_ind.telemetry.iterations
    assert len(its_j) == len(its_i)
    for a, b in zip(its_j, its_i):
        assert (a.k_requested, a.k_granted, a.k_drafted) == \
            (b.k_requested, b.k_granted, b.k_drafted)
        assert a.k_granted == a.k_requested      # bypass: grant == ask
        assert not a.plan_held
        assert (a.t_iter, a.t_draft, a.t_verify, a.t_sample) == \
            (b.t_iter, b.t_draft, b.t_verify, b.t_sample)
    # step telemetry identical too, planner fields included
    for sa, sb in zip(e_joint.telemetry.steps, e_ind.telemetry.steps):
        assert (sa.k_requested, sa.k_granted, sa.preempted,
                sa.held_tests) == (sb.k_requested, sb.k_granted,
                                   sb.preempted, sb.held_tests)
        assert sa.t_step == sb.t_step
        assert sa.t_step_predicted == sb.t_step_predicted
    # and both match the legacy single-request engine's stream
    leg = ServingEngine(cfg, params, NGramDrafter(), max_len=512,
                        temperature=temperature, clock="model", seed=7)
    assert r_joint.tokens == leg.generate(
        prompt, max_new=32, controller=CascadeController()).tokens


def test_engine_policy_planner_consistency(tiny_moe):
    """A supplied planner's config is the policy source of truth: an
    explicit contradicting `policy` argument raises instead of being
    silently ignored, and the engine's `policy` attribute reflects the
    planner actually in use."""
    from repro.core import BatchSpecPlanner, PlannerConfig
    cfg, params = tiny_moe
    pl = BatchSpecPlanner(cfg, config=PlannerConfig(policy="independent"))
    with pytest.raises(ValueError):
        BatchedEngine(cfg, params, lambda: NGramDrafter(), max_batch=1,
                      max_len=128, policy="joint", planner=pl)
    eng = BatchedEngine(cfg, params, lambda: NGramDrafter(), max_batch=1,
                        max_len=128, planner=pl)
    assert eng.policy == "independent"
    with pytest.raises(ValueError):
        BatchedEngine(cfg, params, lambda: NGramDrafter(), max_batch=1,
                      max_len=128, policy="bogus")


def test_legacy_scheduler_works_over_batched_engine(tiny_moe):
    """The legacy FIFO Scheduler is a thin wrapper over batch=1."""
    cfg, params = tiny_moe
    bat = BatchedEngine(cfg, params, lambda: NGramDrafter(), max_batch=1,
                        max_len=256, temperature=0.0, clock="model")
    sched = Scheduler(bat, controller_factory=lambda: StaticKController(2))
    res = sched.run([Request(request_id="a", prompt=[1, 2, 3] * 6,
                             max_new=12),
                     Request(request_id="b", prompt=[4, 5] * 8,
                             max_new=12)])
    assert len(res) == 2
    assert all(len(r.tokens) == 12 for r in res)
    assert sched.tokens_per_second() > 0


# ===================================================================== #
# Continuous batching end-to-end
# ===================================================================== #

def test_continuous_batching_drains_queue_in_order(tiny_moe):
    cfg, params = tiny_moe
    eng = BatchedEngine(cfg, params, lambda: NGramDrafter(), max_batch=2,
                        max_len=256, temperature=0.0, clock="model")
    sched = ContinuousBatchingScheduler(
        eng, controller_factory=lambda: StaticKController(2))
    reqs = [Request(request_id=f"r{i}", prompt=[3 + i, 4 + i] * 8,
                    max_new=8 + 4 * i) for i in range(5)]
    res = sched.run(reqs)
    assert [r.telemetry.request_id for r in res] == [q.request_id
                                                    for q in reqs]
    for r, q in zip(res, reqs):
        assert len(r.tokens) == q.max_new
    tel = eng.telemetry
    assert tel.steps, "engine recorded no steps"
    assert 1.0 <= tel.mean_occupancy <= 2.0
    assert all(s.occupancy <= 2 for s in tel.steps)
    # per-request iteration records carry the batch fields
    its = [it for r in res for it in r.telemetry.iterations]
    assert any(it.batch_occupancy == 2 for it in its)
    assert all(it.batch_occupancy in (1, 2) for it in its)
    if cfg.is_moe:
        assert any(it.union_experts > 0 for it in its)


def test_batched_outputs_match_sequential_greedy(tiny_moe):
    """Greedy decoding is lossless under batching: each request's token
    stream must equal its single-request stream regardless of who shares
    the verification pass."""
    cfg, params = tiny_moe
    reqs = [Request(request_id=f"r{i}", prompt=[3 + i, 5 + i, 7 + i] * 6,
                    max_new=16) for i in range(3)]
    leg = ServingEngine(cfg, params, NGramDrafter(), max_len=256,
                        temperature=0.0, clock="model")
    ref = {q.request_id: leg.generate(
        q.prompt, q.max_new, controller=StaticKController(2)).tokens
        for q in reqs}
    eng = BatchedEngine(cfg, params, lambda: NGramDrafter(), max_batch=3,
                        max_len=256, temperature=0.0, clock="model")
    sched = ContinuousBatchingScheduler(
        eng, controller_factory=lambda: StaticKController(2))
    for r in sched.run(reqs):
        assert r.tokens == ref[r.telemetry.request_id], r.telemetry.request_id


# ===================================================================== #
# Union-packed verification path (docs/kernels.md): bit-identity with
# the dense dispatch at the engine level
# ===================================================================== #

@pytest.mark.parametrize("b", [1, 4])
def test_packed_engine_streams_bit_identical_to_dense(tiny_moe, b):
    """BatchedEngine(packed=True) compacts each pass's expert union into
    `packed_expert_cap` slots but performs the same contractions in the
    same dtype — so every emitted token stream must equal the dense
    engine's bit for bit, at B=1 and under a shared B=4 pass."""
    cfg, params = tiny_moe
    reqs = [Request(request_id=f"r{i}", prompt=[3 + i, 5 + i, 7 + i] * 6,
                    max_new=16) for i in range(max(b, 3))]

    def streams(packed):
        eng = BatchedEngine(cfg, params, lambda: NGramDrafter(),
                            max_batch=b, max_len=256, temperature=0.0,
                            clock="model", seed=0, packed=packed)
        sched = ContinuousBatchingScheduler(
            eng, controller_factory=lambda: CascadeController())
        res = sched.run([Request(request_id=q.request_id,
                                 prompt=list(q.prompt),
                                 max_new=q.max_new) for q in reqs])
        return {r.telemetry.request_id: r.tokens for r in res}, eng

    dense, _ = streams(False)
    packed, eng = streams(True)
    assert dense == packed
    # the packed path actually engaged and reported its slot count
    from repro.models.moe import packed_expert_cap
    caps = [s.packed_experts for s in eng.telemetry.steps]
    assert all(c > 0 for c in caps)
    assert all(c <= cfg.num_experts for c in caps)
    dense_caps = [s.packed_experts for s in streams(False)[1].telemetry.steps]
    assert all(c == 0 for c in dense_caps)


def test_step_telemetry_reports_experts_in_place(tiny_moe):
    """A packed pass whose union cap reaches E reads the stacked experts in
    place and says so: every pass of a B=8 engine (8 rows route 16 >= E
    choices), and no one-token pass of a B=1 engine (U_pad = k < E)."""
    cfg, params = tiny_moe

    def steps(b, k):
        eng = BatchedEngine(cfg, params, lambda: NGramDrafter(),
                            max_batch=b, max_len=64, temperature=0.0,
                            clock="model", seed=0, packed=True)
        ContinuousBatchingScheduler(
            eng, controller_factory=lambda: StaticKController(k)).run(
            [Request(request_id=f"r{i}", prompt=[3 + i, 5, 7] * 3,
                     max_new=4) for i in range(b)])
        assert eng.telemetry.steps
        for s in eng.telemetry.steps:
            assert s.experts_in_place == (s.packed_experts
                                          == cfg.num_experts)
        return [s.experts_in_place for s in eng.telemetry.steps]

    assert all(steps(8, 2))
    assert not any(steps(1, 0))


# ===================================================================== #
# Greedy tokens picked on the device: the host copies [B, T] ids, not
# the [B, T, V] logits
# ===================================================================== #

@pytest.fixture(scope="module")
def tiny_dsv2():
    """A reduced DeepSeek-V2-Lite: MLA, a dense layer 0, YaRN, shared
    experts and un-renormalised top-k."""
    cfg = get_config("deepseek-v2-lite").reduced()
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def _two_requests():
    # prompts of 19 and 27 tokens: three and four chunks of up to 8
    return [Request(request_id=f"r{i}", prompt=[3 + i, 5, 7 + i] * (6 + 3 * i)
                    + [11], max_new=14) for i in range(2)]


def _record_passes(eng):
    """Keep each pass's logits beside the ids the pass picked from them."""
    passes = []
    real = eng._decode

    def decode(*args):
        out = real(*args)
        passes.append((np.asarray(out[0], np.float32),
                       np.asarray(out[2]["greedy_ids"])))
        return out

    eng._decode = decode
    return passes


@pytest.mark.parametrize("model", ["tiny_moe", "tiny_dsv2"])
def test_device_greedy_streams_equal_host_logits_path(model, request):
    """Across chunked prefill and speculative decode, every pass's device
    ids are the host's argmax of that pass's logits, and each greedy
    stream equals the legacy engine's at B = 1, which picks its tokens
    from the logits on the host."""
    cfg, params = request.getfixturevalue(model)
    eng = BatchedEngine(cfg, params, lambda: NGramDrafter(), max_batch=2,
                        max_len=256, temperature=0.0, clock="model",
                        chunk=8, packed=True)
    passes = _record_passes(eng)
    res = ContinuousBatchingScheduler(
        eng, controller_factory=lambda: StaticKController(2)).run(
        _two_requests())
    assert {lo.shape[1] for lo, _ in passes} >= {8}
    assert any(s.prefill_tokens and s.decode_tokens
               for s in eng.telemetry.steps)
    for lo, ids in passes:
        assert ids.dtype == np.int32
        np.testing.assert_array_equal(ids, np.argmax(lo, axis=-1))
    leg = ServingEngine(cfg, params, NGramDrafter(), max_len=256,
                        temperature=0.0, clock="model")
    for q, r in zip(_two_requests(), sorted(
            res, key=lambda r: r.telemetry.request_id)):
        ref = leg.generate(q.prompt, q.max_new,
                           controller=StaticKController(2))
        assert r.tokens == ref.tokens, q.request_id


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_fetch_copies_logits_only_when_sampling(tiny_moe, monkeypatch,
                                                temperature):
    """A greedy step copies ids, flags and counters, with no vocabulary
    axis; a sampled step still copies the logits and rejection-samples."""
    from repro.serving import engine as engine_mod
    cfg, params = tiny_moe
    calls = {"greedy_verify": 0, "rejection_sample": 0}
    for name in calls:
        real = getattr(engine_mod, name)

        def counted(*a, _real=real, _name=name):
            calls[_name] += 1
            return _real(*a)

        monkeypatch.setattr(engine_mod, name, counted)
    b = 2
    eng = BatchedEngine(cfg, params, lambda: NGramDrafter(), max_batch=b,
                        max_len=256, temperature=temperature, clock="model",
                        chunk=8, packed=True, seed=3)
    res = ContinuousBatchingScheduler(
        eng, controller_factory=lambda: StaticKController(2)).run(
        _two_requests())
    assert all(len(r.tokens) == 14 for r in res)
    for s in eng.telemetry.steps:
        t_max = (s.tokens_in_flight + s.padded_tokens) // s.occupancy
        logits_bytes = b * t_max * cfg.vocab_size * 4      # float32 here
        if temperature > 0:
            assert s.d2h_bytes > logits_bytes
        else:
            # int32 ids and bool flags [B, T]; int32 unions [L] and [L, B]
            assert s.d2h_bytes == (b * t_max * (4 + 1)
                                   + cfg.num_layers * (1 + b) * 4)
    sampled = temperature > 0
    assert (calls["rejection_sample"] > 0) == sampled
    assert (calls["greedy_verify"] > 0) != sampled
