"""Device time of the remat recompute of the forward inside the backward
(``rematted_computation``), in ms a traced step: the window's device time
of every operation that ``repro.tracing.stage_of`` places there by its
``op_name`` (``bench.scopes``), over the steps traced.  Nothing where the
program names no stages."""
from bench.scopes import stage_ms


def read(record):
    return stage_ms(record, "recompute")
