"""Host time of the batch build a step, in ms: the mean of the program's
``data.lm_batch`` spans (``repro.data.lm_batch``) inside the traced
window, the part of ``input.ms_per_step`` that is not placement.  Nothing
where the program has no such span."""
from bench.scopes import span_ms


def read(record):
    return span_ms(record, "data.lm_batch")
