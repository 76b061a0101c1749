"""merge_resident_ms_per_compaction: device milliseconds of the
merge.resident executable (kernels/merge_resident.py names it in the trace)
per traced compaction."""
KERNEL = "merge_resident"


def read(ctx):
    exe = ctx.executable(ctx.load("kernels", KERNEL))
    traced = [o for o in ctx.window.get("ops", []) if o.get("traced")]
    if exe is None or not traced:
        return None
    return 1000.0 * exe["seconds"] / len(traced)
