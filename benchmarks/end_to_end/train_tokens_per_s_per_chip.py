"""Tokens of the steps completed in the window over the time those steps
took, over the chips; every step fenced by reading its loss, every step
counted.
"""


def read(run):
    steps = run.client["steps"]
    took = sum(s for s, _ in steps)
    return len(steps) * run.client["tokens_per_step"] / took / run.chips
