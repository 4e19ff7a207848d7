"""State backend: the planned state bytes (the engine's memory report)
over the mean, across the window's decode waves, of the state bytes the
active requests need (keys and values at each slot's fill; a recurrent
state whole per active slot)."""

from harness import counts


def read(ctx):
    planned = ctx.planned_state_bytes
    waves = [c.wave_slots for c in ctx.run.calls if c.wave_slots]
    if not planned or not waves:
        return None
    live = [sum(counts.live_state_bytes(ctx.dims, pos + 1) for pos in w)
            for w in waves]
    return planned / (sum(live) / len(live))
