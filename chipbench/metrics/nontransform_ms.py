"""Device time of ops that are neither local transforms nor collectives
(relayouts, DCT shuffles and scatters, spectral scale) per step, mean over
the chips, in ms."""


def read(run):
    if run.trace is None:
        return None
    return run.trace.class_s.get("other", 0.0) / run.steps * 1e3
