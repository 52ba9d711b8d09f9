"""Device time of ops under a ``stage<k>`` scope (each stage's whole local
transform: FFTs, R2R shuffles and phase, complex split/combine) per step,
mean over the chips, in ms."""
from chipbench import scopes


def read(run):
    return scopes.per_step_ms(run, "stage")
