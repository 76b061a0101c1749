"""twcs_expired_converted_pct: the cells the resident program converted
from expired-TTL to tombstone on the device (the rise of the counter
`compaction.device_expired_converted` over the window's cycles) per
hundred cells the window's merge tasks read. Half of a cycle's cells are
the repaired window's, all expired inside grace: 50 when every one of
them was converted on the device. None for a program without the counter
(it reads 0 there, and no cell merged reads nothing)."""


def read(ctx):
    merged = ctx.window.get("cells_merged")
    converted = ctx.window.get("cells_converted")
    if not merged or not converted:
        return None
    return 100.0 * converted / merged
