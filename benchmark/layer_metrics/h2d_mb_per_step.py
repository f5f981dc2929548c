"""Bytes the window's ``h2d`` spans shipped (their ``nbytes``: the host
batch, images and labels) over its steps, in MB.  What a change of wire
format or batch moves.  Nothing to read from a program whose spans carry
no count."""


def read(ctx):
    shipped = [s["nbytes"] for s in ctx["spans"]
               if s["phase"] == "h2d" and s.get("nbytes") is not None]
    if not shipped or not ctx["window_steps"]:
        return None
    return sum(shipped) / ctx["window_steps"] / 1e6
