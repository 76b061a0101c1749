"""rf3_engine_read_off_cpu_ms_per_read: the part of
rf3_engine_read_ms_per_read during which the reading thread did NOT run:
`engine.read`'s wall less its thread's CPU seconds (children included),
mean over the replica reads of the window, three nodes' threads on one
GIL. None from a program whose spans carry no `cpu` (the parent of
PR 35)."""
SPAN = "engine.read"


def read(ctx):
    import cpu_spans
    import rf3_spans
    return cpu_spans.mean_off_ms(rf3_spans.in_window(ctx.window, [SPAN]))
