"""The local transforms' share of their roofline, %: the least time of a
step's nominal work on one chip (its input read once and its output written
once at peak HBM bandwidth, or its nominal operations at peak FLOP/s,
whichever is longer) over the device time of the local-transform ops."""


def read(run):
    if run.trace is None or not run.trace.op_count.get("local_transform"):
        return None
    least = max(run.bytes_per_step / run.peak["hbm_bytes_per_s"],
                run.flops_per_step / run.peak["flops_per_s"])
    per_step = run.trace.class_s["local_transform"] / run.steps
    return 100.0 * least / per_step
