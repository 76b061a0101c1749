"""rf3_messaging_codec_ms_per_op: milliseconds of internode wire codec
(`messaging.encode` on the sender, `messaging.decode` on the receiving
socket's thread; gossip's messages included) inside the window, per
acknowledged operation."""


def read(ctx):
    import rf3_spans
    return rf3_spans.ms_per(ctx.window, rf3_spans.CODEC,
                            rf3_spans.answered(ctx.window))
