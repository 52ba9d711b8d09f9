"""The 90th percentile (nearest rank) of every step wall in the window, ms."""
import math


def read(run):
    walls = sorted(run.step_s)
    return walls[max(0, math.ceil(0.9 * len(walls)) - 1)] * 1e3
