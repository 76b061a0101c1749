"""write_serialize_ms_per_compaction: device milliseconds of the
write.serialize executable (kernels/write_serialize.py names it in the trace)
per traced compaction."""
KERNEL = "write_serialize"


def read(ctx):
    exe = ctx.executable(ctx.load("kernels", KERNEL))
    traced = [o for o in ctx.window.get("ops", []) if o.get("traced")]
    if exe is None or not traced:
        return None
    return 1000.0 * exe["seconds"] / len(traced)
