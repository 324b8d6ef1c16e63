"""A quantile of a Prometheus histogram family over the measured window:
the growth of each `le` bucket between the window's open and close, then
the quantile by linear interpolation inside the bucket that holds it (as
`histogram_quantile` does; the first bucket starts at 0, a quantile in
`+Inf` reads the last finite bound). Labels select the series, as in
`phase: emit` of `kuiper_boundary_ms`. `None` where nothing was recorded in
the window or the program has no such family."""
import re

_LE = re.compile(r'\ble="([^"]*)"')


def buckets(text: str, family: str, labels: dict) -> dict:
    """`le` bound -> cumulative count, summed over the matching series."""
    wants = [f'{k}="{v}"' for k, v in labels.items()]
    out: dict = {}
    for line in text.splitlines():
        if not line.startswith(family + "_bucket{"):
            continue
        if not all(w in line for w in wants):
            continue
        le = _LE.search(line).group(1)
        bound = float("inf") if le == "+Inf" else float(le)
        out[bound] = out.get(bound, 0.0) + float(line.rsplit(" ", 1)[1])
    return out


def quantile(grown: dict, q: float):
    """`grown`: `le` bound -> cumulative count of the samples of interest."""
    bounds = sorted(grown)
    total = grown[bounds[-1]] if bounds else 0.0
    if total <= 0:
        return None
    rank = q * total
    below, lower = 0.0, 0.0
    for bound in bounds:
        if grown[bound] >= rank:
            if bound == float("inf"):
                return lower
            inside = grown[bound] - below
            share = (rank - below) / inside if inside > 0 else 1.0
            return lower + (bound - lower) * share
        below, lower = grown[bound], bound
    return lower


def read(ctx, family: str, q: float, **labels):
    if ctx.marks0 is None or ctx.marks1 is None:
        return None
    b0 = buckets(ctx.marks0["metrics"], family, labels)
    b1 = buckets(ctx.marks1["metrics"], family, labels)
    return quantile({le: n - b0.get(le, 0.0) for le, n in b1.items()}, q)
