"""Share of the feed's wall time in which it was NOT blocked on host-side
feeding, in percent: 1 - (decode stalls + transfer dispatch) / wall, the
arithmetic of the program's `FeedTelemetry.summarize` (`overlap_frac`),
copied so that the yardstick does not move with the program."""


def reduce(ctx):
    wall = ctx.counters.get("feed.wall_s", 0.0)
    if wall <= 0:
        return None
    blocked = (ctx.counters.get("feed.stall_decode_s", 0.0)
               + ctx.counters.get("feed.transfer_s", 0.0))
    return 100.0 * max(0.0, min(1.0, 1.0 - blocked / wall))
