"""ycsb_merge_resident_roofline: the least time the chip could take for
the served compaction's merge rounds packed inside the traced slice
(kernels/merge_resident.py's least_bytes over the HBM rate of peaks.json:
the cells of each round packed in the slice read once, the kept cells of
each round gathered in the slice written once) over the device time of
the merge.resident executable in the same slice. Memory-bound; None, never
0, when the executable is not in the trace."""
PACK, GATHER = "merge.resident.pack", "merge.resident.gather"


def read(ctx):
    import ycsb_spans
    kernel = ctx.load("kernels", "merge_resident")
    exe = ctx.executable(kernel)
    t0 = ctx.window.get("release_perf")
    if exe is None or t0 is None:
        return None
    tr = ctx.traffic.get("trace", {})
    recs = ycsb_spans.task_records(ctx.window)
    if not recs or "start_s" not in tr:
        return None
    lo = t0 + float(tr["start_s"])
    hi = lo + float(tr["seconds"])
    inside = [r for r in recs if lo <= r["start"] and r["end"] <= hi]
    packs = [r for r in inside if r["name"] == PACK]
    if not packs:
        return None
    need = kernel.least_bytes(
        sum(r["cells"] for r in packs),
        sum(r["cells"] for r in inside if r["name"] == GATHER),
        ctx.window["lanes"])
    return 100.0 * need / ctx.peaks()["hbm_bytes_per_s"] / exe["seconds"]
