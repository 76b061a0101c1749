"""ucs_zstd_compress_ms_per_mib: the host's Zstd work per MiB compacted:
the seconds of `write.compress` (one segment packed and compressed on a
compressor-pool thread; the pool's threads add up) inside the window's
cycles, over the input MiB of the cycles' merge tasks, in milliseconds.
Thread-seconds, not wall: a pool of N hides up to N of them behind each
second of the task."""
SPAN = "write.compress"


def read(ctx):
    ops = ctx.window.get("ops")
    if not ops:
        return None
    import program_spans
    hit = [r for r in program_spans.in_operations(ops) or []
           if r["name"] == SPAN]
    mib = sum(t["bytes_read"] for o in ops for t in o["tasks"]
              if not t.get("dropped")) / 2.0 ** 20
    if not hit or mib <= 0:
        return None
    return 1000.0 * sum(r["end"] - r["start"] for r in hit) / mib
