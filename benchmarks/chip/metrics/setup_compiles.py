"""setup_compiles: XLA compiles in set-up that the persistent compile cache
did not serve, from the program's compile tally before the first
request."""

from chipbench import progspans


def read(ctx):
    tally = progspans.setup_compiles(ctx)
    return None if tally is None else tally[0]
