"""ann_segment_decode_ms_per_query: host milliseconds per vector query spent
decoding sstable segments the chunk cache did not hold (`sstable.read.segment`
in `storage/sstable/reader.py`: pread, CRC, decompress, unshuffle; a point
read builds no key map), summed over the spans inside the window's requests
and divided by ALL of the window's queries, one that decoded nothing
included: what is left of `cql.ann.rows` that is I/O and not interpreter.
None from a program without the span (the parent of PR 26)."""
SPAN = "sstable.read.segment"


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    queries = program_spans.window_queries(ops)
    if not queries or not any(SPAN in q for q in queries):
        return None
    return 1000.0 * sum(q.get(SPAN, 0.0) for q in queries) / len(queries)
