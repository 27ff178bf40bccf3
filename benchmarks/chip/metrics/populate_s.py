"""populate_s: seconds of set-up spent loading the deployment's records
(``lsm.populate`` spans inside ``bench.setup``), from the trace."""

from chipbench import progspans


def read(ctx):
    s = progspans.spans(ctx)
    return None if s is None else s.seconds("lsm.populate", progspans.SETUP)
