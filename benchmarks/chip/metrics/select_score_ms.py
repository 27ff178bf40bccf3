"""select_score_ms: milliseconds per request of arm scoring (``tune.score``
spans: one scorer call each, up to its sync), from the trace."""

from chipbench.progspans import per_request_ms


def read(ctx):
    return per_request_ms(ctx, "tune.score")
