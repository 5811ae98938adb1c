"""Batch occupancy: the mean over the window's engine steps of the slots
that produced a token, over ``max_batch``, in %."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    return 100.0 * sum(st.tokens for st in steps) / (len(steps)
                                                     * run.max_batch)
