"""Device time of one run of the engine's decode program (the jitted
``_decode_impl``: ``LM.decode_step`` plus the masked argmax), from the
trace, in ms per call."""
DECODE = r"_decode_impl"


def read(run):
    if run.trace is None:
        return None
    n, sec = run.trace.module(DECODE)
    return 1e3 * sec / n if n else None
