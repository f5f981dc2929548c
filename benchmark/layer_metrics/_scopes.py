"""What the readers of the program's named scopes share: the device time
of one scope over the traced epochs (``scope_reduce.scope_seconds``, put
into ``ctx["trace"]["scope_s"]`` by the runner), and its share of the
device's busy time.  None where there is no trace or the program's
compiled text named no scope at all (a program from before the scopes);
0.0 where scopes were found and this one has no operation."""


def seconds(ctx, scope):
    tr = ctx.get("trace")
    if not tr or not tr.get("scope_s"):
        return None
    if set(tr["scope_s"]) <= {"-"}:
        return None
    return float(tr["scope_s"].get(scope, 0.0))


def busy_share_pct(ctx, scope):
    secs = seconds(ctx, scope)
    if secs is None or ctx["trace"]["busy_s"] <= 0:
        return None
    return 100.0 * secs / ctx["trace"]["busy_s"]
