"""Block-prediction work (conditionals and simulations) of the window's
sweeps over the device's busy time in the window, as a share of the
roofline."""
from work import roofline_share


def read(run):
    t = run.get("trace")
    if run["phase"] != "uq" or not t:
        return None
    w = run["work"]
    return roofline_share(w["flops"], w["bytes"], t["busy_s_mean"], run["peak"],
                 run["chips"])
