"""twcs_merge_resident_roofline: the least time the chip could take for
the merge rounds of the traced cycle's two compactions (the bytes they
must move, kernels/merge_resident.py at this table's lane count: every
cell read once, every kept cell written once, over the HBM rate of
peaks.json) over the device time of the merge.resident executable in the
trace. The program that also converts expired cells reads against the
same floor as stcs_lz4.major's. Memory-bound; None, never 0, when the
executable is not in the trace."""


def read(ctx):
    kernel = ctx.load("kernels", "merge_resident")
    exe = ctx.executable(kernel)
    traced = [o for o in ctx.window.get("ops", []) if o.get("traced")]
    if exe is None or not traced:
        return None
    need = sum(kernel.least_bytes(t["cells_read"], t["cells_written"],
                                  ctx.window["lanes"])
               for o in traced for t in o["tasks"] if not t.get("dropped"))
    return 100.0 * need / ctx.peaks()["hbm_bytes_per_s"] / exe["seconds"]
