"""The token-major grouped GEMM's share of its roofline, in %: the least
time (``lib.work.grouped_least_s``) of the expert GEMMs the window required
-- top-k routed rows per token, the weights of the experts that receive a
token -- for each prefill and each decode step, over the device time of
the operations named ``grouped_gemm`` (the kernel's ``pallas_call`` name,
which the trace gives as the HLO instruction's: ``%grouped_gemm.3 = ...``);
nothing where no such operation ran."""
import re

from lib import work
from lib.trace import PALLAS

KERNEL = re.compile(r"^%grouped_gemm(\.\d+)?$")


def read(run):
    if run.trace is None:
        return None
    sec = sum(s for op, (_, s) in run.trace.ops.items()
              if PALLAS in op and KERNEL.match(op.partition(" = ")[0]))
    if not sec:
        return None
    cfg, peaks = run.spec, run.peaks
    least = sum(work.grouped_least_s(cfg, p - 1, peaks)
                for p in run.window_prefills())
    least += sum(work.grouped_least_s(cfg, st.tokens, peaks)
                 for st in run.window_steps())
    return 100.0 * least / sec
