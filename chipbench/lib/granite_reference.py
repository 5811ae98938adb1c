"""The plain reference of a Granite mixture: ``lib/reference.py``'s forward,
which runs the four multipliers as published, with a weight table of its
own.

Under ``reference.init`` a random Granite block decodes one token over and
over: its own input token (the x12 embedding of the current token outweighs
the branches' x0.22, and the tied head ranks it first), or, with a smaller
embedding, one token for the whole answer (at the 1/64 attention scale the
scores barely spread, every position averages the same prefix, and the
positions' states fall together).  The served tokens then sit at margins
that no rounding moves, so a program in float8 reads the same gaps as one
in bfloat16.  This table gives each part the scale that the plain block's
table gives it, undoing the multipliers in the draw, not in the forward:

- the embedding at ``initializer_range / embedding_multiplier``, so the
  residual stream starts where the plain block's does;
- ``wq`` and ``wk`` times ``(attention_multiplier * sqrt(head_dim)) **
  -0.5``, so the scores spread as at the ``1/sqrt(head_dim)`` scale;
- ``wo`` over ``residual_multiplier``, so each attention branch adds what a
  plain one does;
- ``expert_down`` times ``EXPERT_GAIN / residual_multiplier``;
- the router times ``ROUTER_GAIN``.

The last two set how far routing moves the answer.  The gated sum of eight
experts at near-even gates is about a third of one dense MLP's output, too
little to keep the positions apart; at a dense MLP's scale, a token whose
eighth and ninth experts swap under bfloat16 rounding moves its state by an
eighth of a large output, and the bfloat16 program leaves the reference's
argmax as often as a float8 one does.  In between, a sharper router
(eighth and ninth gates small beside the first) and each expert at
``sqrt(2)`` of a dense output keep the answers varied (about 30 distinct
tokens in 40) and the bfloat16 program near the reference while float8
drifts: on the CPU at a quarter of the width, 16 layers and 40 experts,
the mean gap read 20 times lower for bfloat16 than for float8.
"""
from __future__ import annotations

from lib import reference
from lib.reference import (  # noqa: F401  (a reference module's interface)
    PROGRAM_FIELD,
    PROGRAM_LEAF,
    logits_at,
    program_config,
    shapes,
)

#: each expert's output over what fan-in scaling gives, beyond undoing
#: ``residual_multiplier``
EXPERT_GAIN = 2.0 ** 0.5
#: the router's standard deviation over fan-in scaling
ROUTER_GAIN = 3.0


def init(spec, name: str, shape: tuple) -> tuple[float, float]:
    """(mean, standard deviation) of the weight ``name``'s normal draw:
    ``reference.init``'s, rescaled as the module docstring says."""
    mean, std = reference.init(spec, name, shape)
    m = spec.multiplier
    if name == "embed":
        std = std / m("embedding_multiplier")
    elif name in ("wq", "wk"):
        std *= (m("attention_multiplier") * spec.head_dim ** 0.5) ** -0.5
    elif name == "wo":
        std /= m("residual_multiplier")
    elif name == "expert_down":
        std *= EXPERT_GAIN / m("residual_multiplier")
    elif name == "router":
        std *= ROUTER_GAIN
    return mean, std
