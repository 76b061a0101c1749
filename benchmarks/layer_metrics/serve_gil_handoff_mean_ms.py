"""serve_gil_handoff_mean_ms: gil_handoff_mean_ms for the serving cells:
the mean over the probe's beats inside the window (from the release to the
last completion), while the `cql-exec` workers, the nodes' dispatch pools
and the compactions' threads share the one GIL. PR 30 inferred 0.7-0.9 ms
a GIL-releasing call under the readers' convoy; this reads it. None from
a program without the probe (the parent of PR 35)."""


def read(ctx):
    import cpu_spans
    return cpu_spans.handoff_mean_ms(ctx.window)
