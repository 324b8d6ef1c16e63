"""How late the generator ran: publish time minus due time, a quantile over
the publishes of the measured window. A starved generator must not read as
a fast engine."""
from readers.quantile import quantile


def read(ctx, q: float):
    late = [(o - d) * 1e3 for o, d in zip(
        ctx.out[ctx.first_publish:ctx.end_publish],
        ctx.due[ctx.first_publish:ctx.end_publish])]
    return quantile(late, q) if late else None
