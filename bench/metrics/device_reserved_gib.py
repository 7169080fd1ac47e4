"""Peak bytes reserved for the loaded programs' temporaries on the
fullest device, in GiB (`memory_stats()`'s `peak_bytes_reserved`): the
boost program's `memory_analysis()` temp size. Apart from the buffers
in use (`device_peak_gib`), and held together with them."""


def read(ctx):
    peak = ctx["memory"]["reserved"]
    return peak / 2 ** 30 if peak else None
