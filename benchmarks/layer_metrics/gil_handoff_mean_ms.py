"""gil_handoff_mean_ms: milliseconds one release-and-retake of the GIL
took, mean over the beats of the program's probe thread (`gil-probe`: ten
`runtime.gil.handoff` records a second, each one `time.sleep(0)` timed on
the wall) that lie inside the window's operations. What any thread of the
process paid, at that instant, for one GIL-releasing call. None from a
program without the probe (the parent of PR 35)."""


def read(ctx):
    import cpu_spans
    return cpu_spans.handoff_mean_ms(ctx.window)
