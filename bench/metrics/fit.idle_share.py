"""Share of the fit's window in which no operation ran on a chip, mean
over chips."""


def read(run):
    t = run.get("trace")
    if run["phase"] != "fit" or not t:
        return None
    return 100.0 * (1.0 - t["busy_s_mean"] / t["window_s"])
