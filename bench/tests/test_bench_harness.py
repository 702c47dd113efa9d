"""A whole run of a small cell on the CPU, past the harness's look for a
chip: what the window serves is correct; with the timed path broken
underneath (a token altered where it is produced, a step that returns its
cache unchanged), or read through the control (the reference computed
in the precision below the configuration's), `correct` comes out
false."""

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec as specs  # noqa: E402
from bench.harness import run_cell  # noqa: E402

DATA = Path(__file__).parent / "data"
BENCH = specs.load_json(ROOT / "BENCHMARK.json")
SEED = 2 ** 31 + 77


def tiny_cell():
    return specs.Cell("tiny.cell", 1, specs.load_json(DATA / "tiny-moe.json"),
                      specs.load_json(DATA / "tiny-mix.json"),
                      BENCH["end_to_end"], BENCH["per_layer"])


def run(seed=SEED, log=lambda s: None, **kw):
    from repro.core.cost_model import TPU_V5E
    return run_cell(tiny_cell(), seed=seed, seconds=3.0, trace=False,
                    t_start=time.perf_counter(),
                    peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
                    hw=TPU_V5E, log=log, **kw)


def test_sound_run_is_correct():
    lines = []
    out = run(log=lines.append)
    # set-up warmed every span length the window ran
    assert ", 0 programs lowered inside" in lines[0]
    assert out["correct"] is True
    assert out["checks"]["max_gap"]["value"] == 0.0
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tok_s", "itl_p95_ms", "ttft_p50_ms",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-2] == "checks"


def test_control_is_not_correct():
    lines = []
    out = run(control=True, log=lines.append)
    assert out["correct"] is False
    assert out["checks"]["max_gap"]["value"] > \
        out["checks"]["max_gap"]["limit"]
    # the program served soundly; only the control's tokens failed
    assert "'served_max_gap': 0.0" in lines[2]


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serving import engine

    real = engine.greedy_verify

    def altered(logits, drafts):
        res = real(logits, drafts)
        res.next_token = (res.next_token + 1) % logits.shape[-1]
        return res

    monkeypatch.setattr(engine, "greedy_verify", altered)
    assert run()["correct"] is False


def test_step_that_keeps_its_cache_is_not_correct(monkeypatch):
    from repro.models import transformer as T

    real = T.decode_step

    def unchanged(cfg, params, cache, tokens, **kw):
        logits, _, aux, staged = real(cfg, params, cache, tokens, **kw)
        return logits, cache, aux, staged

    monkeypatch.setattr(T, "decode_step", unchanged)
    assert run()["correct"] is False


def test_no_finished_request_is_not_correct():
    from bench.check import check_served
    conf = specs.load_json(DATA / "tiny-moe.json")
    check = check_served(conf, None, SEED, [],
                         specs.load_json(DATA / "tiny-mix.json"))
    assert not check.correct
    assert "no request finished" in check.lines()[-1]


@pytest.mark.parametrize("limit,correct", [(None, False), (0.5, True),
                                           (0.1, False)])
def test_limits_decide(limit, correct):
    from bench.check import Check
    c = Check({"max_gap": 0.2}, {"max_gap": limit}, {})
    assert c.correct is correct


@pytest.mark.parametrize("lower", ["float8_e4m3fn", "bfloat16"])
def test_lowered_operands_are_the_precision_below(lower):
    """The control's operands: float8 e4m3 after scaling each output
    column's largest |w| to 448, within float8's rounding of it; bfloat16
    exactly w's rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench.reference.moe_decoder import _lowered
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 32), jnp.float32)
    got = np.asarray(_lowered(w, 0, lower))
    wn = np.asarray(w)
    if lower == "float8_e4m3fn":
        s = np.max(np.abs(wn), axis=0, keepdims=True) / 448
        # e4m3 keeps 3 bits of mantissa: half a step is 1/16 of the value
        # (of 2**-9 * s below the smallest normal)
        assert (np.abs(got - wn) <= np.maximum(np.abs(wn) / 16,
                                                2.0 ** -10 * s)
                * (1 + 1e-6)).all()
        assert np.max(np.abs(got / s), axis=0) == pytest.approx(448)
        assert len(np.unique(np.round(got / s, 4))) < wn.size
    else:
        want = np.asarray(w.astype(jnp.bfloat16).astype(jnp.float32))
        assert (got == want).all()
    assert not (got == np.asarray(w)).all()
