"""Output tokens that became visible to the clients inside the window, of
requests that did not fail, over the window's seconds.
"""
from benchmarks import readers


def read(run):
    return readers.window_tokens(run) / run.client["seconds"]
