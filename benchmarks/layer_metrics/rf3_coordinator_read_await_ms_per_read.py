"""rf3_coordinator_read_await_ms_per_read: milliseconds a coordinator
spent parked in `coordinator.read.await` (its own replica read, the
digest READ_REQ sent, waiting for block_for responses; a digest
mismatch's second round included) per `coordinator.read` of the window,
mean."""


def read(ctx):
    import rf3_spans
    return rf3_spans.await_ms_per_request(ctx.window, "read")
