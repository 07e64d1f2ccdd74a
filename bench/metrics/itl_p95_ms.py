"""95th percentile of every gap between streamed tokens that ended in
the window."""
from bench.measures import itls_ms, percentile


def read(run):
    return percentile(itls_ms(run), 95)
