"""Share of the boundaries in the measured window that one path served, from
`*_emit_sources` in the rule's status: `device` means the pre-issued fetch
had landed when the boundary came."""


def _sources(status: dict) -> dict:
    key = next((k for k in status if k.endswith("_emit_sources")), None)
    return dict(status[key]) if key else {}


def read(ctx, source: str):
    if ctx.marks0 is None or ctx.marks1 is None:
        return None
    a, b = _sources(ctx.marks0["status"]), _sources(ctx.marks1["status"])
    total = sum(b.values()) - sum(a.values())
    if total <= 0:
        return None
    return 100.0 * (b.get(source, 0) - a.get(source, 0)) / total
