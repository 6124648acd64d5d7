"""Which code drives a cell of which ``kind``."""

from benchmark.harness import serve, train

DRIVERS = {"train": train.run, "serve": serve.run}
