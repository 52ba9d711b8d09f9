"""Device time of ops classed ``local_transform`` (op_classes.json) per
step, mean over the chips, in ms."""


def read(run):
    if run.trace is None or not run.trace.op_count.get("local_transform"):
        return None
    return run.trace.class_s["local_transform"] / run.steps * 1e3
