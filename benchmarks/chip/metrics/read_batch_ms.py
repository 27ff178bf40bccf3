"""read_batch_ms: milliseconds per request of the engine's point-read
batches (``engine.read`` spans), from the trace."""

from chipbench.progspans import per_request_ms


def read(ctx):
    return per_request_ms(ctx, "engine.read")
