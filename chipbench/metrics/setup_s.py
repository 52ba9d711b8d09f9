"""Seconds from process start to the first measured step: import, device
init, plan build (compile or cache load), fields, warm-up."""


def read(run):
    return run.setup_s
