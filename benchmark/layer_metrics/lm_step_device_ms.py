"""Device-busy time an optimizer step of a token-trained cell: the union
of the intervals in which an operation ran on the device, over the steps
of the traced epochs (one step program a step: 2 x 8,192 tokens in the
first such cell).  Device trace."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("steps"):
        return None
    return 1000.0 * tr["busy_s"] / tr["steps"]
