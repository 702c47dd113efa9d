"""Serving substrate: rejection-sampler exactness, n-gram drafter, and the
key end-to-end invariant — greedy speculative output == greedy plain
output, token for token, regardless of K policy."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic in-repo fallback (requirements-dev.txt)
    from tests._hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.core import CascadeController, StaticKController
from repro.models import transformer as T
from repro.serving import NGramDrafter, ServingEngine
from repro.serving.drafter import DraftModelDrafter
from repro.serving.sampler import greedy_verify, rejection_sample


# ===================================================================== #
# Rejection sampler
# ===================================================================== #

def test_rejection_preserves_target_distribution_point_drafts():
    """With a deterministic (n-gram) drafter, the emitted first token must
    be distributed exactly as the target distribution."""
    rng = np.random.default_rng(0)
    v = 5
    p = np.array([0.5, 0.2, 0.15, 0.1, 0.05])
    draft_tok = 0
    counts = np.zeros(v)
    n = 40_000
    for _ in range(n):
        res = rejection_sample(rng, np.stack([p, p]), [draft_tok], None)
        tok = res.accepted[0] if res.n_accepted else res.next_token
        counts[tok] += 1
    emp = counts / n
    np.testing.assert_allclose(emp, p, atol=0.01)


def test_rejection_preserves_target_distribution_stochastic_drafts():
    """Leviathan guarantee with a stochastic drafter q != p."""
    rng = np.random.default_rng(1)
    p = np.array([0.6, 0.3, 0.1])
    q = np.array([0.2, 0.3, 0.5])
    counts = np.zeros(3)
    n = 40_000
    for _ in range(n):
        d = int(rng.choice(3, p=q))
        res = rejection_sample(rng, np.stack([p, p]), [d], np.stack([q]))
        tok = res.accepted[0] if res.n_accepted else res.next_token
        counts[tok] += 1
    np.testing.assert_allclose(counts / n, p, atol=0.01)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(0, 7), min_size=1, max_size=5),
       st.integers(0, 2**31 - 1))
def test_rejection_accepted_is_prefix(drafts, seed):
    rng = np.random.default_rng(seed)
    k = len(drafts)
    p = rng.dirichlet(np.ones(8), size=k + 1)
    res = rejection_sample(rng, p, drafts, None)
    assert res.accepted == drafts[:res.n_accepted]
    assert 0 <= res.n_accepted <= k
    assert 0 <= res.next_token < 8


def test_greedy_verify_matches_argmax():
    logits = np.array([[0, 3, 1], [5, 0, 0], [0, 0, 9], [1, 2, 0]],
                      np.float32)
    res = greedy_verify(logits, [1, 0, 0])
    assert res.accepted == [1, 0]
    assert res.next_token == 2  # argmax of row 2 (first mismatch position)
    res2 = greedy_verify(logits, [1, 0, 2])
    assert res2.n_accepted == 3 and res2.next_token == 1  # bonus row


# ===================================================================== #
# N-gram drafter
# ===================================================================== #

def test_ngram_drafter_finds_repetition():
    d = NGramDrafter(max_ngram=3)
    hist = [1, 2, 3, 4, 5, 1, 2, 3]
    drafts, probs = d.propose(hist, 3)
    assert drafts == [4, 5, 1]
    assert probs is None


def test_ngram_drafter_prefers_longest_match():
    d = NGramDrafter(max_ngram=3)
    hist = [9, 2, 3, 7, 7, 7, 1, 2, 3, 5, 5, 1, 2, 3]
    drafts, _ = d.propose(hist, 2)
    assert drafts == [5, 5]  # trigram [1,2,3] match beats bigram/unigram


def test_ngram_drafter_no_match():
    d = NGramDrafter()
    drafts, _ = d.propose([1, 2, 3, 4, 5], 4)
    assert drafts == [] or len(drafts) <= 4  # unigram fallback allowed
    drafts, _ = d.propose([1], 4)
    assert drafts == []


# ===================================================================== #
# End-to-end greedy equivalence (speculation must be lossless)
# ===================================================================== #

@pytest.mark.parametrize("controller_factory", [
    lambda: StaticKController(3),
    lambda: CascadeController(),
])
def test_speculative_greedy_equals_plain_greedy(tiny_moe, controller_factory):
    cfg, params = tiny_moe
    prompt = [5, 6, 7, 8, 9] * 6
    eng = ServingEngine(cfg, params, NGramDrafter(), max_len=256,
                        temperature=0.0, clock="model", seed=0)
    ref = eng.generate(prompt, max_new=24, controller=StaticKController(0))
    out = eng.generate(prompt, max_new=24, controller=controller_factory())
    assert out.tokens == ref.tokens


def test_draft_model_drafter_end_to_end(tiny_moe):
    cfg, params = tiny_moe
    # the target itself as (perfect) drafter: every draft must be accepted
    drafter = DraftModelDrafter(cfg, params, max_len=256, temperature=0.0)
    eng = ServingEngine(cfg, params, drafter, max_len=256,
                        temperature=0.0, clock="model", seed=0)
    prompt = list(range(3, 23))
    ref = eng.generate(prompt, max_new=16, controller=StaticKController(0))
    out = eng.generate(prompt, max_new=16, controller=StaticKController(4))
    assert out.tokens == ref.tokens
    etr = out.telemetry.etr
    assert etr > 3.0, f"perfect drafter should accept ~all drafts, etr={etr}"


def test_engine_telemetry_breakdown(tiny_moe):
    cfg, params = tiny_moe
    eng = ServingEngine(cfg, params, NGramDrafter(), max_len=256,
                        temperature=0.0, clock="model")
    res = eng.generate([1, 2, 3] * 8, max_new=12,
                       controller=StaticKController(2))
    tel = res.telemetry
    assert tel.output_tokens >= 12 - 1
    bd = tel.breakdown()
    assert bd["verify"] > 0 and bd["total"] >= bd["verify"]
    assert all(i.unique_experts >= cfg.experts_per_token
               for i in tel.iterations)


# ===================================================================== #
# The serving loop the launcher and the chip smoke run share
# ===================================================================== #

def test_serve_loop_finishes_every_request(tiny_moe):
    """`launch.serve.serve` on the wall clock with chunked prefill: more
    requests than rows, so rows retire and re-admit; every request ends
    with its budget of in-vocabulary tokens, in submission order."""
    from repro.core import TPU_V5E
    from repro.launch.serve import (acceptance, mean_granted_k,
                                    mixed_requests, serve)
    cfg, params = tiny_moe
    reqs = mixed_requests(cfg, 10, seed=3, max_new=6)
    rep = serve(cfg, params, reqs, hw=TPU_V5E)
    assert [r.telemetry.request_id for r in rep.results] == \
        [r.request_id for r in reqs]
    for r in rep.results:
        assert len(r.tokens) == 6
        assert all(0 <= t < cfg.vocab_size for t in r.tokens)
    assert 0 < rep.first_token_s <= rep.wall_s
    assert rep.scheduler.engine.clock == "wall"
    assert 0.0 <= acceptance(rep.results) <= 1.0
    assert mean_granted_k(rep.results) >= 0.0


def test_engine_refuses_non_finite_logits(tiny_moe):
    """A pass whose logits hold NaN must stop the engine, not be sampled."""
    from repro.serving import BatchedEngine
    cfg, params = tiny_moe
    bad = dict(params, final_norm={"scale": jnp.full_like(
        params["final_norm"]["scale"], jnp.nan)})
    eng = BatchedEngine(cfg, bad, max_batch=1, max_len=128, chunk=8)
    eng.join([5, 6, 7, 8], max_new=4)
    with pytest.raises(FloatingPointError, match="non-finite"):
        eng.step()


def test_compile_cache_location(tmp_path, monkeypatch):
    """Entry points keep JAX's persistent cache where
    JAX_COMPILATION_CACHE_DIR says and set nothing themselves; without it,
    at a fixed `.jax_cache` under the checkout."""
    from repro.launch.serve import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "e"))
        assert use_compile_cache(tmp_path) == str(tmp_path / "e")
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache(tmp_path) == str(tmp_path / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            tmp_path / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
