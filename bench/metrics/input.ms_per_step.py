"""Host time of the input pipeline a step, in ms: the mean of the
harness's ``bench.input`` spans (``repro.data.lm_batch`` and the batch's
``device_put``) over the traced steps."""


def read(record):
    spans = record["trace"]["spans_s"].get("bench.input")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
