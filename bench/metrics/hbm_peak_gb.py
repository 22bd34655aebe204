"""Device: peak bytes in use on the fullest chip after the window, in GB
(1e9 bytes), as the runtime reports it (``memory_stats``)."""


def read(ctx):
    b = ctx.get("memory_peak_bytes")
    return b / 1e9 if b else None
