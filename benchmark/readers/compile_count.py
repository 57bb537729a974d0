"""Programs JAX compiled inside the window (jax.monitoring). Expected 0:
every shape is warmed in set-up."""


def read(run):
    return len(run["compiles"])
