"""How late the load generator submitted the requests due in the window:
the 95th percentile of submit time minus due time, in ms (host clock).  The
loop submits only between engine steps, so a long step shows here."""
from lib.stats import percentile


def read(run):
    lags = [lag for due, lag in run.record.lateness
            if run.start <= due < run.end]
    return 1e3 * percentile(lags, 95) if lags else None
