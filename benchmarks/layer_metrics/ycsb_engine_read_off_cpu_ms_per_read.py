"""ycsb_engine_read_off_cpu_ms_per_read: the part of
ycsb_engine_read_ms_per_read during which the reading thread did NOT run:
`engine.read`'s wall less its thread's CPU seconds (children included),
mean over the window's read requests. Waiting for the GIL, the chunk
cache's and the store's locks, or a pread. None from a program whose spans
carry no `cpu` (the parent of PR 35)."""
SPAN = "engine.read"


def read(ctx):
    import cpu_spans
    return cpu_spans.request_off_ms(ctx.window, SPAN)
