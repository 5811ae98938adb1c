"""Queue wait in the engine: the 95th percentile, over the requests due in
the window, of the engine's admission stamp (``Request.t_admit``, after the
request's prefill) minus the due time, in ms; a request not admitted by the
window's end counts at its elapsed time."""
from lib.stats import percentile


def read(run):
    vals = [((s.admit if s.admit is not None and s.admit <= run.end
              else run.end) - s.due) for s in run.due_in_window()]
    return 1e3 * percentile(vals, 95) if vals else None
