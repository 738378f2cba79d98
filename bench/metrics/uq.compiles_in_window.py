"""Backend compiles or cache loads inside the UQ window: a chunk shape
the warm-up did not meet."""


def read(run):
    return float(run["window_compiles"]) if run["phase"] == "uq" else None
