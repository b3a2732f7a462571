"""The benchmark's yardstick: peaks, work arithmetic, traffic, trace
reduction, plain references and the comparison. Nothing here imports
arithmetic from ``accelerate_tpu``: later PRs may change the program and may
not change this."""
