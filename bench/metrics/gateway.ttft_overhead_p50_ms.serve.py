"""Median over requests of the client's TTFT from send minus the engine's
t_first - t_submit: the gateway's and the async front-end's share."""
from bench.measures import median, ttft_overhead_ms


def read(run):
    return median(ttft_overhead_ms(run))
