"""comp_pages_per_write: pages written by flushes and compactions per
write over the window, from the engine's ``IOStats``."""


def read(ctx):
    io = ctx.counters["io"]
    w = io.queries["w"]
    return io.comp_pages_written / w if w else None
