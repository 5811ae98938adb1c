"""The grouped (per-expert) Pallas GEMM's share of its roofline, in %: the
least time (``lib.work.grouped_least_s``) of the expert GEMMs the window
required -- top-k routed rows per token, the weights of the experts that
receive a token -- for each prefill and each decode step, over the device
time of ``_grouped_kernel`` (in the trace, the Pallas calls with a rank-3
or rank-4 result)."""
from lib import work

#: the grouped kernel has a rank-3 result, rank 4 under vmap
RANKS = (3, 4)


def read(run):
    if run.trace is None:
        return None
    n, sec = run.trace.pallas(RANKS)
    if not n:
        return None
    cfg, peaks = run.spec, run.peaks
    least = sum(work.grouped_least_s(cfg, p - 1, peaks)
                for p in run.window_prefills())
    least += sum(work.grouped_least_s(cfg, st.tokens, peaks)
                 for st in run.window_steps())
    return 100.0 * least / sec
