"""Seconds of the fit's host structure pass (mini-batch k-means, filtered
NNS, packing), from the fit's own ``stream_stats["struct_time_s"]``."""


def read(run):
    return run["struct_s"] if run["phase"] == "fit" else None
