"""The paged decode attention kernel's share of its roofline, in %."""
from bench.measures import paged_decode_roofline


def read(run):
    got = paged_decode_roofline(run)
    return None if got is None else got[0]
