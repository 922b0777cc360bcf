"""Programs lowered inside the window (a ``jax.monitoring`` listener on
every jaxpr-to-MLIR lowering).  Should read 0."""


def read(run):
    return run.compiles
