"""Device milliseconds per step of the sparsify-and-aggregate round
(operations raised from it, collectives apart; see ``bench/trace.py``),
averaged over the chips (layer: round)."""


def read(ctx):
    s = ctx.trace.layer_s.get("round")
    if not s or ctx.steps == 0:
        return None
    return 1e3 * s / ctx.steps
