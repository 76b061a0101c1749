"""index_ann_roofline: the least time the chip could take per query (the
float32 matrix read once from HBM, kernels/index_ann.py, over the HBM rate
of peaks.json) over the device time of the index.ann executable per call
in the traced slice. Memory-bound: 0.5 flop per byte."""


def read(ctx):
    kernel = ctx.load("kernels", "index_ann")
    exe = ctx.executable(kernel)
    if exe is None or not exe["calls"]:
        return None
    d = ctx.config["data"]
    least_s = kernel.least_bytes(d["rows"], d["dim"]) \
        / ctx.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s * exe["calls"] / exe["seconds"]
