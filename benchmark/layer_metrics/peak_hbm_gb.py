"""Peak device memory on the fullest chip after the window, in GB: live
arrays plus what the runtime reserved for the running program's
temporaries (``memory_stats()``: ``peak_bytes_in_use`` +
``peak_bytes_reserved``)."""


def read(ctx):
    peak = ctx.get("memory_peak_bytes")
    return None if not peak else peak / 1e9
