"""Peak bytes of buffers in use on the fullest device, in GiB
(`memory_stats()`'s `peak_bytes_in_use`): the frame, the binned matrix,
the models. The programs' temporaries are `device_reserved_gib`; the
run's `memory_peak_bytes` is the two together."""


def read(ctx):
    peak = ctx["memory"]["in_use"]
    return peak / 2 ** 30 if peak else None
