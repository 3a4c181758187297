"""The whole step's share of the chip's bf16 peak, in %: the step's least
FLOPs (``bench.workcount``) times the steps completed in the traced window,
over the window and the peak."""


def read(record):
    t = record["trace"]
    if t["steps"] <= 0 or t["window_s"] <= 0:
        return None
    flops = record["work"]["step_flops"] * t["steps"]
    return 100.0 * flops / t["window_s"] / record["peaks"]["bf16_flops"]
