"""merge_resident_roofline: the least time the chip could take for the
traced compactions' merge rounds (bytes they must move, kernels/
merge_resident.py, over the HBM rate of peaks.json) over the device time
of the merge.resident executable in the trace. Memory-bound."""


def read(ctx):
    kernel = ctx.load("kernels", "merge_resident")
    exe = ctx.executable(kernel)
    traced = [o for o in ctx.window.get("ops", []) if o.get("traced")]
    if exe is None or not traced:
        return None
    need = sum(kernel.least_bytes(o["cells_read"], o["cells_written"],
                                  ctx.config["lanes"]) for o in traced)
    least_s = need / ctx.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / exe["seconds"]
