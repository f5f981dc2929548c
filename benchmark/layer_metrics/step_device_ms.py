"""Device-busy time an optimizer step: the union of the intervals in
which an operation ran on a device, averaged over the devices, over the
steps of the traced epochs.  Device trace."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    return 1000.0 * tr["busy_s"] / tr["steps"]
