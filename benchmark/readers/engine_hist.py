"""A percentile of the engine's own ingest-to-emit histogram
(`e2e_latency_ms` in the rule's status, log-bucketed, at most 6.25 % off) —
the engine's side of the clock, a cross-check of the client's."""


def read(ctx, key: str):
    if ctx.marks1 is None:
        return None
    value = (ctx.marks1["status"].get("e2e_latency_ms") or {}).get(key)
    return float(value) if value else None
