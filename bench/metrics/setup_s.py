"""setup_s: process start to window start (JAX start-up, instance and
deployment build, warm-up calls, compiles or compile-cache loads)."""


def read(run):
    return run.setup_s
