"""Device time of cross-chip collectives over the window, mean over
chips. Nothing is read on one chip."""


def read(run):
    t = run.get("trace")
    if not t or run["chips"] < 2:
        return None
    return 100.0 * t["collective_s_mean"] / t["window_s"]
