"""Device time of the backward pass (under ``transpose(``, the recompute
aside), in ms a traced step: the window's device time of every operation
that ``repro.tracing.stage_of`` places there by its ``op_name``
(``bench.scopes``), over the steps traced.  Nothing where the program
names no stages."""
from bench.scopes import stage_ms


def read(record):
    return stage_ms(record, "backward")
