"""Imbalance of the experts held here: the busiest held expert's
assignments over the mean over the held experts, over the window, in the
expert layer where that is largest (in a deployment the busiest
expert's chip is the one the exchange waits for).  The program's routing counters.  None
where the program counts no routing."""


def read(ctx):
    routing = ctx.get("routing")
    if not routing:
        return None
    worst = None
    for layer in routing.values():
        a = layer["assignments"]
        if a.sum() > 0:
            ratio = float(a.max() / a.mean())
            worst = ratio if worst is None else max(worst, ratio)
    return worst
