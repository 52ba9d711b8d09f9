"""Device time of ops in no plan executable (the entry's eager ops, such as
the solve's divide, null-mode scatter and ``real``) per step, mean over the
chips, in ms."""
from chipbench import scopes


def read(run):
    return scopes.per_step_ms(run, "entry")
