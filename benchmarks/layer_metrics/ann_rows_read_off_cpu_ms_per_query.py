"""ann_rows_read_off_cpu_ms_per_query: the part of
ann_rows_read_ms_per_query during which the `cql-exec` thread did NOT run:
`cql.ann.rows`' wall less its thread's CPU seconds (the segment decodes
below it included: LZ4 and CRC with the GIL released are CPU), mean over
the window's queries. None from a program whose spans carry no `cpu` (the
parent of PR 35)."""
SPAN = "cql.ann.rows"


def read(ctx):
    import cpu_spans
    return cpu_spans.query_off_ms(ctx.window.get("ops"), SPAN)
