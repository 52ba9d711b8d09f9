"""Device time of all-to-all ops per step, mean over the chips, in ms."""


def read(run):
    if run.trace is None or not run.trace.op_count.get("all_to_all"):
        return None
    return run.trace.class_s["all_to_all"] / run.steps * 1e3
