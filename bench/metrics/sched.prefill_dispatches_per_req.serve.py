"""Prefill-program executions per request whose prefill finished, in the
traced window."""
from bench.measures import prefill_dispatches_per_request


def read(run):
    return prefill_dispatches_per_request(run)
