"""Device milliseconds per step of the collectives (the payloads'
all-gather and the loss's all-reduce, by opcode; see ``bench/trace.py``),
averaged over the chips (layer: wire). One chip runs no collective, and
the reader returns None there."""


def read(ctx):
    s = ctx.trace.layer_s.get("collective")
    if not s or ctx.steps == 0:
        return None
    return 1e3 * s / ctx.steps
