"""flush_compact_ms: milliseconds per request of the engine's flushes,
with the compactions each triggers (``engine.flush`` spans), from the
trace."""

from chipbench.progspans import per_request_ms


def read(ctx):
    return per_request_ms(ctx, "engine.flush")
