"""Seconds from process start to the opening of the window: imports,
weights, engine construction (W8A8 calibration included), compiling or
loading every tick program, and the traffic's warm-up."""


def read(run):
    return run.setup_s
