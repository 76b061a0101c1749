"""ycsb_read_p99_ms: 99th percentile of the closed loop's read latency
(sent to answered, the generator's clock) over every acknowledged read of
the window."""


def read(ctx):
    lat = [(o["done"] - o["sent"]) * 1000.0
           for o in ctx.window.get("ops", [])
           if o.get("ok") and o.get("kind") == "read"]
    return ctx.stats.percentile(lat, 99) if lat else None
