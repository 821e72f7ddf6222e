"""Process start to the start of the window: reaching the device, weights,
warm-up, correctness checks, compilation and (serving) the pre-roll.
"""


def read(run):
    return run.setup_s
