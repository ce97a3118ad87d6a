"""XLA compiles inside the window (JAX's ``backend_compile`` events): the
serving path compiling shapes the warm-up could not cover."""


def read(run):
    return run.compiles
