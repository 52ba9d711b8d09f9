"""Device time of ops inside a plan executable (``jit(repro_fft_*)``) but
under neither a stage nor a hop scope (the input parameter's split,
relayouts between scopes) per step, mean over the chips, in ms."""
from chipbench import scopes


def read(run):
    return scopes.per_step_ms(run, "plan")
