"""io_decode_phase_pct: the `io_decode` phase of CompactionTask.profile over the
tasks' wall, summed over the window's compactions. The phase is host
thread-seconds: a stage that runs on a pool adds its threads up, so the
figure passes 100 where the pool hides the work behind the wall."""
PHASE = "io_decode"


def read(ctx):
    ops = ctx.window.get("ops") or []
    wall = sum(o["wall_s"] for o in ops)
    if wall <= 0 or not any(PHASE in o["profile"] for o in ops):
        return None
    return 100.0 * sum(o["profile"].get(PHASE, 0.0) for o in ops) / wall
