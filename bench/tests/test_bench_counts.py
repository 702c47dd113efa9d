"""The benchmark's own operation and byte counts (bench/flops.py) for both
configurations, against hand arithmetic, and the sizes they rest on
against the program's parameter tree."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import flops  # noqa: E402
from bench.model import arch_of  # noqa: E402
from bench.spec import BENCH_DIR, load_json  # noqa: E402

PEAKS = load_json(BENCH_DIR / "peaks.json")["TPU v5 lite"]


def arch(name):
    return arch_of(load_json(BENCH_DIR / "configs" / f"{name}.json"))


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
    from bench.model import program_config
    from repro.models import transformer as T
    conf = load_json(BENCH_DIR / "configs" / f"{name}.json")
    a, cfg = arch_of(conf), program_config(conf)
    tree = jax.eval_shape(functools.partial(T.init_params, cfg),
                          jax.random.PRNGKey(0))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    params = (flops.dense_params(a)
              + a.layers * a.experts * flops.expert_params(a)
              + a.vocab * a.d_model)
    assert params * a.dtype_bytes == nbytes
