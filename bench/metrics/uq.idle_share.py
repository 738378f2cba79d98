"""Share of the UQ window in which no operation ran on the chip."""


def read(run):
    t = run.get("trace")
    if run["phase"] != "uq" or not t:
        return None
    return 100.0 * (1.0 - t["busy_s_mean"] / t["window_s"])
