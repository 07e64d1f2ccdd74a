"""Output tokens that reached clients inside the window, per second."""
from bench.measures import window_tokens


def read(run):
    return window_tokens(run) / run.seconds
