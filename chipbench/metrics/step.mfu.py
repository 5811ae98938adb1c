"""The whole serving step's share of the chip's bf16 peak, in %: the model
FLOPs every prompt and output token processed in the window requires
(``lib.work``: weights, causal attention over its context, and the logits
head for output tokens) over the device's busy seconds in the window times
the peak.  Busy time rather than the window's length, so that a faster step
shows here at a fixed offered load."""
from lib import work


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    cfg = run.spec
    flops = sum(work.prefill_flops(cfg, p - 1) for p in run.window_prefills())
    flops += sum(work.decode_flops(cfg, st.positions)
                 for st in run.window_steps())
    return 100.0 * flops / (run.trace.busy_s
                            * run.peaks["bf16_flops_per_s"])
