"""Device ms of the served executables (the AOT-compiled ``jit_fn``
modules) per dispatch, in the traced part of the window."""


def read(run):
    return run.executor_ms_per_dispatch()
