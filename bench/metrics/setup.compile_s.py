"""Seconds of lowering and backend compiles (persistent-cache loads
included) before the window, from JAX's monitoring events."""


def read(run):
    return run["setup_compile_s"]
