"""ann_query_p95_ms: 95th percentile of the closed loop's per-query
latency (sent to answered, generator's clock), over every query of the
window. Tens of queries: a description, not a deciding tail."""


def read(ctx):
    lat = [(o["done"] - o["sent"]) * 1000.0
           for o in ctx.window.get("ops", []) if o.get("ok")]
    return ctx.stats.percentile(lat, 95) if lat else None
