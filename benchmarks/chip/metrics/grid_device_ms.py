"""grid_device_ms: device milliseconds per request of the tuner grid
programs (``core/batch.py`` ``_solve_many``), from the trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    runs, seconds = ctx.trace.module_seconds("_solve_many")
    return seconds / ctx.requests * 1e3 if runs else None
