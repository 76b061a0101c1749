"""rf3_coordinator_write_await_ms_per_update: milliseconds a coordinator
spent parked in `coordinator.write.await` (its own replica applied, the
MUTATION_REQs sent, waiting for block_for acknowledgements) per
`coordinator.write` of the window, mean."""


def read(ctx):
    import rf3_spans
    return rf3_spans.await_ms_per_request(ctx.window, "write")
