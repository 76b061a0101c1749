"""rf3_replica_handle_ms_per_op: milliseconds of replica-side handling
(`messaging.handle.mutation_req` + `messaging.handle.read_req`, on the
nodes' dispatch pools: the engine.write or engine.read of a replica that
is not the coordinator) inside the window, per acknowledged operation."""


def read(ctx):
    import rf3_spans
    return rf3_spans.ms_per(ctx.window, rf3_spans.HANDLE,
                            rf3_spans.answered(ctx.window))
