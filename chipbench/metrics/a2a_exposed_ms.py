"""The part of all-to-all device time during which no other op ran on that
chip, per step, mean over the chips, in ms."""


def read(run):
    if run.trace is None or not run.trace.op_count.get("all_to_all"):
        return None
    return run.trace.exposed_a2a_s / run.steps * 1e3
