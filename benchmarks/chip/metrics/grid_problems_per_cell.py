"""grid_problems_per_cell: tuner problems solved per tuning returned: the
``problems`` of the ``tune.grid`` spans inside requests over the cells the
requests returned, from the trace."""

from chipbench import progspans


def read(ctx):
    s = progspans.spans(ctx)
    grids = s.inside("tune.grid") if s is not None else []
    cells = sum(r[2] for r in ctx.records)
    if not grids or not cells:
        return None
    return sum(int(g.stats["problems"]) for g in grids) / cells
