"""Seconds of set-up that JAX spent tracing, lowering and compiling or
loading programs from the persistent cache (its monitoring events)."""


def read(run):
    return run.setup_compile_s
