"""Forward-plus-gradient work of the window's steps over the device's
busy time in the window (mean over chips), as a share of the roofline."""
from work import roofline_share


def read(run):
    t = run.get("trace")
    if run["phase"] != "fit" or not t:
        return None
    w = run["work"]
    return roofline_share(w["flops"], w["bytes"], t["busy_s_mean"], run["peak"],
                 run["chips"])
