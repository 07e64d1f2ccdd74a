"""Device time of one decode-program execution, mean over the trace."""
from bench.measures import decode_device_ms


def read(run):
    return decode_device_ms(run)
