"""The planned dense Pallas GEMMs' share of their roofline, in %: the sum of
the least times (``lib.work.gemm_least_s``) of the dense GEMMs the window
required -- the MLP projections of each prefilled prompt token and each
decoded token, and the logits head of each decoded token -- over the device
time of the Pallas GEMM kernels (``_k_inner_kernel``, ``_k_step_kernel``;
in the trace, the Pallas calls with a rank-2 result)."""
from lib import work

#: the dense GEMM kernels have rank-2 results (lib.trace.Reduction.pallas)
RANKS = (2,)


def read(run):
    if run.trace is None:
        return None
    n, sec = run.trace.pallas(RANKS)
    if not n:
        return None
    cfg, peaks = run.spec, run.peaks
    least = sum(work.dense_gemm_least_s(cfg, p - 1, 0, peaks)
                for p in run.window_prefills())
    least += sum(work.dense_gemm_least_s(cfg, st.tokens, st.tokens, peaks)
                 for st in run.window_steps())
    return 100.0 * least / sec
