"""select_ms: milliseconds per request of arm selection
(``api/compile.py`` ``select_arms``), from the program's own
``Report.walls["select_s"]``."""


def read(ctx):
    s = ctx.counters.get("select_s")
    return sum(s) / len(s) * 1e3 if s else None
