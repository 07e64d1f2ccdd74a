"""Model FLOPs of the step programs' tokens over their device time at the
chip's bf16 peak, in %."""
from bench.measures import step_mfu


def read(run):
    return step_mfu(run)
