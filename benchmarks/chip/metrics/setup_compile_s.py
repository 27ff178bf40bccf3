"""setup_compile_s: seconds of set-up spent tracing, lowering and compiling
(or loading from the persistent cache), from the program's compile tally
before the first request; overlapping (nested) events count once."""

from chipbench import progspans


def read(ctx):
    tally = progspans.setup_compiles(ctx)
    return None if tally is None else tally[1]
