"""Process start to the trainer's first completed dispatch: one step
where batches stream, the first scanned epoch where they are resident
(the smallest unit the host can see).  Host clock."""


def read(ctx):
    return ctx.get("first_step_s")
