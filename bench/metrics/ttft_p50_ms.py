"""Median time to first token over every request due in the window,
from the moment it was due."""
from bench.measures import median, ttfts_ms


def read(run):
    return median(ttfts_ms(run))
