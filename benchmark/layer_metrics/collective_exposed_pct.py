"""Share of the collectives' time during which no other operation ran on
that device: what overlap with the backward pass could still hide.
Device trace."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["collective_s"] <= 0:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["collective_s"]
