"""Forward likelihood work of the window's steps over the summed device
time of the Pallas log-likelihood kernel's events, as a share of the
roofline. Nothing is read where the kernel did not run."""
from work import roofline_share
from trace_metrics import kernel_s

KERNEL = r"sbv_loglik_pallas.*\[tpu_custom_call\]"


def read(run):
    t = run.get("trace")
    if run["phase"] != "fit" or not t:
        return None
    w = run["work"]
    return roofline_share(w["kernel_flops"], w["kernel_bytes"], kernel_s(t, KERNEL),
                 run["peak"])
