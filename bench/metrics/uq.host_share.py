"""Share of the UQ window spent in the host index build, query NNS and
packing, from ``Prediction.stats["host_s"]`` summed over the sweeps."""


def read(run):
    if run["phase"] != "uq":
        return None
    return 100.0 * run["host_s"] / run["window_s"]
