"""Seconds from process start to the first tick of the window: start-up,
weights, plan, compile (or compile-cache loads) and warm-up."""


def read(run):
    return run.setup_s
