"""Host clock from the call into the entry until it returned, before the
block, per step, in ms (traced run)."""


def read(run):
    return sum(run.dispatch_s) / run.steps * 1e3
