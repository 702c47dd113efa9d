"""The benchmark's own operation and byte counts (bench/flops.py) for both
configurations, against hand arithmetic; the sizes they rest on against
the program's parameter tree; and the weights a seed gives, pinned."""

import hashlib
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import archs, flops  # noqa: E402
from bench.spec import BENCH_DIR, load_json  # noqa: E402

PEAKS = load_json(BENCH_DIR / "peaks.json")["TPU v5 lite"]
TINY = BENCH_DIR / "tests" / "data" / "tiny-moe.json"


def arch(name):
    conf = load_json(BENCH_DIR / "configs" / f"{name}.json")
    return archs.for_config(conf).arch_of(conf)


def test_olmoe_counts_by_hand():
    a = arch("olmoe-1b-7b.8l")
    # per layer: q,k,v,o 4*2048*2048; router 2048*64; 8 experts of
    # 3*2048*1024; head 2048*50304; attention 4*16*128 per position
    assert flops.flops_per_token(a, 0) == 1_281_949_696
    assert flops.flops_per_token(a, 99) == (
        2 * (8 * (4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024)
             + 2048 * 50304) + 4 * 8 * 16 * 128 * 100)
    # dense: 8 * (attention + router + 2 norms) + final norm + head
    assert flops.dense_params(a) == (8 * (16_777_216 + 131_072 + 4096)
                                     + 2048 + 103_022_592)
    # 3 live tokens over rows of 10 and 20 cached positions, 8 experts
    # per layer: weights + KV (2 * 8 layers * 16 heads * 128 * 2 bytes
    # per position, read and written) + embedding row in, logits out
    got = flops.pass_bytes(a, 3, [10, 20], 8.0)
    want = (2 * (flops.dense_params(a) + 8 * 8 * 6_291_456)
            + 65_536 * (30 + 3) + 3 * (2048 + 50304) * 2)
    assert got == pytest.approx(want, rel=1e-12)


def test_olmoe_scope_counts_by_hand():
    a = arch("olmoe-1b-7b.8l")
    got = flops.scope_counts(a, 3, [10, 20], 8.0)
    # attention: q,k,v,o at 2 operations per multiply-add, and 4 * 16
    # heads * 128 per position at the mean context (15) + 1; ln1 and the
    # projections once, the KV of 30 cached and 3 live positions
    assert got["attention"] == (
        8 * 3 * (2 * 4 * 2048 * 2048 + 4 * 16 * 128 * 16),
        2 * 8 * (4 * 2048 * 2048 + 2048) + 65_536 * 33)
    # moe_ffn: router and 8 of 3 * 2048 * 1024 per token; the router and
    # the union of 8 experts once
    assert got["moe_ffn"] == (
        2 * 8 * 3 * (2048 * 64 + 8 * 6_291_456),
        2 * 8 * (2048 * 64 + 8 * 6_291_456))
    # what the scopes leave to the pass: the head, ln2, the final norm,
    # and the embedding and logits rows
    rest = (flops.pass_flops(a, 3, [10, 20])
            - sum(f for f, _ in got.values()))
    assert rest == 3 * 2 * 2048 * 50304
    rest = (flops.pass_bytes(a, 3, [10, 20], 8.0)
            - sum(b for _, b in got.values()))
    assert rest == pytest.approx(
        2 * (8 * 2048 + 2048 + 2048 * 50304 + 3 * (2048 + 50304)),
        rel=1e-12)


def test_mixtral_counts_by_hand():
    a = arch("mixtral-8x7b.4l")
    assert flops.attn_params(a) == 41_943_040
    assert flops.expert_params(a) == 176_160_768
    assert flops.flops_per_token(a, 0) == 3_416_588_288
    b = flops.pass_bytes(a, 1, [100], 2.0)
    assert b == 598_024_192 + 2_818_572_288 + 1_654_784 + 72_192
    # one token needs the bytes: 3.4 GB at 819 GB/s
    t = flops.least_time(flops.pass_flops(a, 1, [100]), b, PEAKS)
    assert t == pytest.approx(b / 819e9)


@pytest.mark.parametrize("name", ["olmoe-1b-7b.8l", "mixtral-8x7b.4l"])
def test_counts_cover_the_programs_weights(name):
    """Dense weights, all experts and the embedding add up to the bytes
    of the parameter tree the program builds for the configuration."""
    import functools

    import jax
    from repro.models import transformer as T
    conf = load_json(BENCH_DIR / "configs" / f"{name}.json")
    model = archs.for_config(conf)
    a, cfg = model.arch_of(conf), model.program_config(conf)
    tree = jax.eval_shape(functools.partial(T.init_params, cfg),
                          jax.random.PRNGKey(0))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    params = (flops.dense_params(a)
              + a.layers * a.experts * flops.expert_params(a)
              + a.vocab * a.d_model)
    assert params * a.dtype_bytes == nbytes


#: sha256 of the tiny configuration's weights at `WEIGHTS_SEED`, leaf by
#: leaf in path order: a change to the draws, the keys or the layout of
#: the benchmark's weights changes it
WEIGHTS_SHA256 = ("aa4ec1283bf02c5fdf03f7424112a230"
                  "58249f7caf2ff1a915c8585a5ec67c22")
WEIGHTS_SEED = 2 ** 31 + 77


def test_weights_of_a_seed_are_pinned():
    import jax
    import numpy as np
    conf = load_json(TINY)
    model = archs.for_config(conf)
    tree = model.program_params(model.arch_of(conf), WEIGHTS_SEED)
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_flatten_with_path(tree)[0],
                             key=lambda p: jax.tree_util.keystr(p[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256
