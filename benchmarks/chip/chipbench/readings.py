"""Arithmetic that several metric readers share."""

from __future__ import annotations


def rate(ctx) -> float:
    """Units completed per second, from the first request's start to the
    last one's end."""
    records = ctx.records
    units = sum(r[2] for r in records)
    return units / (records[-1][1] - records[0][0])


def idle_pct(ctx):
    """The share of the traced window in which no operation ran on the
    device, in percent (None without a trace)."""
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def io_per_op(ctx):
    """Logical page I/Os per operation over the window, weighed as
    ``lsm.execute_session`` weighs them (the paper's Table 5 measure)."""
    c = ctx.counters
    io, f_a, f_seq = c["io"], c["f_a"], c["f_seq"]
    ops = c["reads"] + c["writes"]
    if not ops:
        return None
    pages = io.random_reads + f_seq * io.seq_reads \
        + f_seq * (io.comp_pages_read + f_a * io.comp_pages_written)
    return pages / ops


def reads_per_lookup(ctx):
    """Random page reads per point lookup over the window."""
    io = ctx.counters["io"]
    lookups = io.queries["z0"] + io.queries["z1"]
    return io.random_reads / lookups if lookups else None
