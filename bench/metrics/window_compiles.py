"""Programs JAX lowered inside the window (from `jax.monitoring`'s
lowering events); every shape is warmed in set-up, so this reads 0."""


def read(run):
    return run.compiles
