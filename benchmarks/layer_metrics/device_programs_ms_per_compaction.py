"""device_programs_ms_per_compaction: device seconds of every executable in
the traced slice (the union of device-op intervals) per traced
compaction, in milliseconds."""


def read(ctx):
    traced = [o for o in ctx.window.get("ops", []) if o.get("traced")]
    if not ctx.trace or not traced or ctx.trace["busy_s"] <= 0:
        return None
    return 1000.0 * ctx.trace["busy_s"] / len(traced)
