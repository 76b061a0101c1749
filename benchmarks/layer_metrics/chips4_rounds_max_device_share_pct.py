"""chips4_rounds_max_device_share_pct: the busiest chip's share of the
`merge.resident` rounds dispatched from the release until the served
compactions finished, from the program's per-device counters
(`merge.resident.rounds.dev<i>`, the driver's `device_counters`). Three
nodes each on a chip of its own read about 33; every round on one chip
reads 100. None from a program without the counters."""
PREFIX = "merge.resident.rounds.dev"


def read(ctx):
    rounds = [v for n, v in (ctx.window.get("device_counters") or {}).items()
              if n.startswith(PREFIX) and v > 0]
    if not rounds:
        return None
    return 100.0 * max(rounds) / sum(rounds)
