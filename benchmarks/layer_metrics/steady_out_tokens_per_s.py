"""Output tokens visible inside the window over its seconds. Below the knee
this only repeats the offered rate; it is recorded, not judged.
"""
from benchmarks import readers


def read(run):
    return readers.window_tokens(run) / run.client["seconds"]
