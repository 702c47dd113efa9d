import jax
import numpy as np
import pytest

# Tests run on the single host CPU device (the 512-device override is only
# ever set inside the dry-run subprocess).


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def tiny_moe():
    """A reduced Mixtral-family MoE shared across tests (init is slow on
    one core; do it once)."""
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config("mixtral-8x7b").reduced()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


COPY_PERIOD = 32


def copy_batch(rng, bs=16, period=COPY_PERIOD, seq=96, vocab=128):
    """Periodic-copy task: [BOS, p, p, p...] — the minimal structure a
    2-layer model learns quickly (fixed-offset attention) and that n-gram
    drafting accelerates at serving time."""
    import jax.numpy as jnp
    import numpy as np
    p = rng.integers(3, vocab, (bs, period))
    reps = seq // period + 2
    full = np.concatenate([np.ones((bs, 1), int)]
                          + [p] * reps, axis=1)[:, :seq + 1]
    toks = full[:, :seq].astype(np.int32)
    labels = full[:, 1:seq + 1].astype(np.int32)
    mask = np.zeros((seq,), np.float32)
    mask[period:] = 1.0  # score only the predictable copy region
    return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels),
            "mask": jnp.broadcast_to(jnp.asarray(mask), (bs, seq))}


@pytest.fixture(scope="session")
def trained_tiny_moe():
    """A tiny MoE trained on the periodic-copy task so that its greedy
    generations are genuinely n-gram-draftable (real acceptance, real
    routing)."""
    import dataclasses
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.training import make_train_step
    from repro.training.optimizer import adamw

    cfg = dataclasses.replace(get_config("mixtral-8x7b").reduced(),
                              vocab_size=128, num_layers=2)
    init_state, step = make_train_step(cfg, optimizer=adamw(3e-3))
    state = init_state(jax.random.PRNGKey(1))
    step = jax.jit(step)
    rng = np.random.default_rng(3)
    first = None
    for _ in range(200):
        state, m = step(state, copy_batch(rng))
        if first is None:
            first = float(m["ce"])
    return cfg, state[0], (first, float(m["ce"]))
