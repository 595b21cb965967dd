"""setup_s: process start to the first timed operation (JAX start-up,
inputs, compile-cache loads, warm-up)."""


def read(run):
    return run.setup_s
