"""Event-to-emit latency from the client's side, a quantile over all the
boundaries that closed in the measured window.

The sink stamps window k's arrival t_k. The running sum of the rows the
windows hold, C_k, is how many sent rows have been answered; rows are folded
in send order, so row C_k is the last that contributed to window k and lies
in publish number ceil(C_k / drain_rows) - 1. Latency of window k is t_k
minus the time that publish was *due* on the generator's schedule. That
leaves out the window's length, takes in every queue on the way, and needs
nothing from inside the engine.
"""
import math

from readers.quantile import quantile


def latencies_ms(arrivals, window_rows, due, drain_rows: int,
                 t_open: float, t_close: float) -> list:
    """Latency of each window that arrived in [t_open, t_close]: a boundary
    that closes after the feed has stopped holds a last row that waited for
    the clock, not for the engine, and is left out."""
    out = []
    answered = 0
    for t, rows in zip(arrivals, window_rows):
        answered += rows
        last = math.ceil(answered / drain_rows) - 1
        if rows > 0 and t_open <= t <= t_close and last < len(due):
            out.append((t - due[last]) * 1e3)
    return out


def read(ctx, q: float):
    lat = latencies_ms([w.t for w in ctx.windows],
                       [w.rows for w in ctx.windows], ctx.due,
                       ctx.pool.drain_rows, ctx.t_open, ctx.t_close)
    return quantile(lat, q) if len(lat) >= 2 else None
