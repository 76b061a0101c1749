"""ycsb_segment_decode_ms_per_read: milliseconds of `sstable.read.segment`
(one chunk-cache miss: pread, CRC, decompress, unshuffle) summed inside
the window's read requests, over ALL of them, one that decoded nothing
included. Updates decode nothing and are left out."""
SPAN = "sstable.read.segment"


def read(ctx):
    import ycsb_spans
    return ycsb_spans.mean_ms_per_request(ctx.window, SPAN, "read")
