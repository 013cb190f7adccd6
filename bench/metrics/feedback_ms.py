"""Device milliseconds per step under the round's ``spa.feedback``
scope (decode, the sent vector, the error state for the next round),
averaged over the chips (layer: round)."""


def read(ctx):
    s = ctx.trace.scope_s.get("spa.feedback")
    if not s or ctx.steps == 0:
        return None
    return 1e3 * s / ctx.steps
