"""Time a device spends in collective operations (the gradient
all-reduce) an optimizer step.  Device trace; nothing to read on one
chip, where the program holds no collective."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["collective_s"] <= 0:
        return None
    return 1000.0 * tr["collective_s"] / tr["steps"]
