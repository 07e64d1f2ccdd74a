"""Traffic mixes: ``<mix>.json`` files of parameters, read by the one
general generator in ``bench.traffic.generate``."""
