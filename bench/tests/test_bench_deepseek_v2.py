"""The DeepSeek-V2 architecture module and its reference: a whole run of a
small cell on the CPU is correct against the reference, and its control
(the reference in the precision below the configuration's) is not; the
counts of the 8-layer DeepSeek-V2-Lite against hand arithmetic; the
counted parameters against the program's tree; the weights a seed gives,
pinned; the departures `program_config` refuses; and the `dense_ffn_ms`
reader on a reduced trace."""

import copy
import functools
import hashlib
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import archs, flops  # noqa: E402
from bench import spec as specs  # noqa: E402
from bench.harness import run_cell  # noqa: E402

DATA = Path(__file__).parent / "data"
TINY = DATA / "tiny-dsv2.json"
LITE = specs.BENCH_DIR / "configs" / "deepseek-v2-lite.8l.json"
BENCH = specs.load_json(ROOT / "BENCHMARK.json")
SEED = 2 ** 31 + 77


def _model(path):
    conf = specs.load_json(path)
    model = archs.for_config(conf)
    return conf, model, model.arch_of(conf)


def run(control=False, log=lambda s: None):
    from repro.core.cost_model import TPU_V5E
    cell = specs.Cell("tiny.dsv2", 1, specs.load_json(TINY),
                      specs.load_json(DATA / "tiny-mix.json"),
                      BENCH["end_to_end"], BENCH["per_layer"])
    return run_cell(cell, seed=SEED, seconds=3.0, trace=False,
                    t_start=time.perf_counter(),
                    peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
                    hw=TPU_V5E, control=control, log=log)


@pytest.mark.parametrize("control", [False, True], ids=["sound", "control"])
def test_served_run_against_the_reference(control):
    """The program's reduced DeepSeek-V2-Lite (a dense layer, then an MoE
    layer with a shared expert, YaRN MLA, top-2 of 4 unrenormalised),
    served through `BatchedEngine`, gives the reference's greedy token at
    every compared position; the control's tokens do not."""
    lines = []
    out = run(control=control, log=lines.append)
    assert ", 0 programs lowered inside" in lines[0]
    assert "'served_max_gap': 0.0" in lines[2]
    assert out["correct"] is (not control)
    if control:
        assert out["checks"]["max_gap"]["value"] > \
            out["checks"]["max_gap"]["limit"]


def test_lite_counts_by_hand():
    _, _, a = _model(LITE)
    assert (a.layers, a.dense_layers, a.moe_layers) == (8, 1, 7)
    # MLA: w_q 2048 x 16 x 192, latent and rope key 2048 x (512 + 64),
    # w_uk and w_uv 512 x 16 x 128 each, wo 16 x 128 x 2048
    attn = (2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256
            + 16 * 128 * 2048)
    assert flops.attn_params(a) == attn == 13_762_560
    assert flops.expert_params(a) == 3 * 2048 * 1408
    # the latent and the rope key, 576 values a position and layer
    assert flops.kv_bytes_per_position(a) == 8 * 576 * 2 == 9216
    # attention and norms (ln1, ln2, latent) x 8; the dense FFN of width
    # 10944; router and the two shared experts (one FFN of 2816) x 7;
    # final norm; head
    dense = (8 * (attn + 2 * 2048 + 512) + 3 * 2048 * 10944
             + 7 * (2048 * 64 + 3 * 2048 * 2816) + 2048 + 2048 * 102400)
    assert flops.dense_params(a) == dense
    # one token: the matrices it meets (6 routed of 64 and the shared
    # experts in each MoE layer), and per earlier position and layer the
    # absorbed attention, 16 heads x (512 + 64 for the score, 512 for
    # the values)
    f0 = 2 * (8 * attn + 3 * 2048 * 10944
              + 7 * (2048 * 64 + 6 * 3 * 2048 * 1408 + 3 * 2048 * 2816)
              + 2048 * 102400)
    assert flops.flops_per_token(a, 0) == f0 + 8 * 2 * 16 * 1088
    assert flops.flops_per_token(a, 99) == f0 + 8 * 2 * 16 * 1088 * 100
    got = flops.pass_bytes(a, 3, [10, 20], 12.0)
    want = (2 * (dense + 7 * 12 * 3 * 2048 * 1408) + 9216 * 33
            + 3 * (2048 + 102400) * 2)
    assert got == pytest.approx(want, rel=1e-12)


def test_lite_scope_counts_by_hand():
    _, _, a = _model(LITE)
    attn, expert = flops.attn_params(a), flops.expert_params(a)
    shared, dense_ffn = 3 * 2048 * 2816, 3 * 2048 * 10944
    got = flops.scope_counts(a, 3, [10, 20], 12.0)
    # attention: projections at 2 operations per multiply-add and the
    # absorbed attention at the mean context (15) + 1; ln1, the latent
    # norm and the projections once, the latent cache of 30 cached and 3
    # live positions
    assert got["attention"] == (
        8 * 3 * (2.0 * attn + 2 * 16 * 1088 * 16),
        8 * (attn + 2048 + 512) * 2 + 9216 * 33)
    # moe_ffn: router, 6 routed and the shared experts per token; the
    # router, the union of 12 and the shared experts once, 7 layers
    assert got["moe_ffn"] == (
        2.0 * 7 * 3 * (2048 * 64 + 6 * expert + shared),
        7 * (2048 * 64 + shared + 12 * expert) * 2)
    # the dense FFN's weights reach its operations by copies outside the
    # scope, so it has no count of its own
    assert set(got) == {"attention", "moe_ffn"}
    # what the scopes leave to the pass: the dense FFN, the head, ln2 and
    # the final norm, and the embedding and logits rows
    rest = flops.pass_flops(a, 3, [10, 20]) - sum(f for f, _ in got.values())
    assert rest == pytest.approx(
        2.0 * 3 * dense_ffn + 3 * 2 * 2048 * 102400, rel=1e-12)
    rest = (flops.pass_bytes(a, 3, [10, 20], 12.0)
            - sum(b for _, b in got.values()))
    assert rest == pytest.approx(
        2 * (dense_ffn + 8 * 2048 + 2048 + 2048 * 102400
             + 3 * (2048 + 102400)), rel=1e-12)


@pytest.mark.parametrize("path", [LITE, TINY], ids=["lite", "tiny"])
def test_counts_cover_the_programs_weights(path):
    """Dense weights, all routed experts and the embedding add up to the
    bytes of the parameter tree the program builds; the benchmark's
    weights have that tree's layout."""
    import jax
    from repro.models import transformer as T
    conf, model, a = _model(path)
    cfg = model.program_config(conf)
    tree = jax.eval_shape(functools.partial(T.init_params, cfg),
                          jax.random.PRNGKey(0))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    params = (flops.dense_params(a)
              + a.moe_layers * a.experts * flops.expert_params(a)
              + a.vocab * a.d_model)
    assert params * a.dtype_bytes == nbytes
    drawn = jax.eval_shape(lambda: model._program_params(a, 5))
    assert jax.tree.structure(drawn) == jax.tree.structure(tree)
    assert jax.tree.leaves(jax.tree.map(
        lambda x, y: (x.shape, x.dtype) == (y.shape, y.dtype),
        drawn, tree)) == [True] * len(jax.tree.leaves(tree))


#: sha256 of the tiny configuration's weights at `SEED`, leaf by leaf in
#: path order: a change to the draws, the keys or the layout of the
#: benchmark's weights changes it
WEIGHTS_SHA256 = ("0d034cfcdcbd5e383fba1eea152c557e"
                  "6be53c8cea698f2a8ad7a7e144974ea3")


def test_weights_of_a_seed_are_pinned():
    import jax
    import numpy as np
    _, model, a = _model(TINY)
    tree = model.program_params(a, SEED)
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_flatten_with_path(tree)[0],
                             key=lambda p: jax.tree_util.keystr(p[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256
    # the reference regenerates each layer's leaves as the program got them
    dense = model.layer_weights(a, SEED, 0)
    moe = model.layer_weights(a, SEED, 1)
    assert (np.asarray(dense["ffn_up"])
            == np.asarray(tree["dense_blocks"]["ffn"]["w_up"][0])).all()
    assert (np.asarray(moe["w_q"])
            == np.asarray(tree["blocks"]["attn"]["w_q"][0])).all()
    assert (np.asarray(moe["shared_down"])
            == np.asarray(tree["blocks"]["moe"]["shared"]["w_down"][0])).all()


@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", True), ("first_k_dense_replace", 0),
    ("n_shared_experts", 1), ("moe_intermediate_size", 1024),
    ("kv_lora_rank", 256), ("rope_theta", 20000), ("rope_scaling", None),
    ("rms_norm_eps", 1e-5), ("routed_scaling_factor", 2.0),
    ("q_lora_rank", 1536), ("scoring_func", "sigmoid"),
    ("topk_method", "group_limited_greedy"), ("num_key_value_heads", 8)])
def test_program_config_refuses_a_departure(key, value):
    conf, model, _ = _model(LITE)
    model.program_config(conf)
    conf = copy.deepcopy(conf)
    conf[key] = value
    with pytest.raises(ValueError):
        model.program_config(conf)


def test_yarn_departures_are_refused():
    conf, model, _ = _model(LITE)
    for key, value in [("factor", 32), ("mscale_all_dim", 1.0),
                       ("beta_fast", 16), ("type", "linear")]:
        bad = copy.deepcopy(conf)
        bad["rope_scaling"][key] = value
        with pytest.raises(ValueError):
            model.program_config(bad)


def test_dense_ffn_ms_reads_every_stack_under_the_scope(monkeypatch):
    """The scope is outside `stagereduce.SCOPES`: its operations come
    keyed by their whole stack, with or without a nested call; where none
    carries it, nothing is read."""
    import bench.stagereduce as stagereduce
    st = SimpleNamespace(base=SimpleNamespace(pass_s=[0.01] * 4), scope_s={
        "attention": 0.004, "moe_ffn": 0.02,
        "while/body/closed_call/dense_ffn": 0.0012,
        "while/body/closed_call/dense_ffn/jit(silu)": 0.0004,
        "while/body": 0.001})
    monkeypatch.setattr(stagereduce, "for_run", lambda run: st)
    reader = specs.load_reader("dense_ffn_ms")
    assert reader(None) == pytest.approx(1e3 * 0.0016 / 4)
    del st.scope_s["while/body/closed_call/dense_ffn"]
    del st.scope_s["while/body/closed_call/dense_ffn/jit(silu)"]
    assert reader(None) is None
    monkeypatch.setattr(stagereduce, "for_run", lambda run: None)
    reader = specs.load_reader("dense_ffn_ms")
    assert reader(None) is None
