"""Device time of the engine's prefill programs (the jitted ``LM.prefill``
of each length bucket) per 1000 prompt tokens prefilled in the window, in
ms.  The engine prefills all but a prompt's last token, which its first
decode step feeds."""
PREFILL = r"^jit_fn$"


def read(run):
    if run.trace is None:
        return None
    n, sec = run.trace.module(PREFILL)
    tokens = sum(p - 1 for p in run.window_prefills())
    return 1e6 * sec / tokens if n and tokens else None
