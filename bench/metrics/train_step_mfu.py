"""The whole job's share of the chips' peak: the least seconds the
chips could take for every histogram level of every tree of the
window's finished jobs (`work.job_min_seconds`, from the cell's shapes),
over the window's seconds. It still bounds a gain after a later PR
takes a kernel off the path."""

import work


def read(ctx):
    res = ctx["result"]
    done = res["attempted"] - res["failed"]
    if not done:
        return None
    least = done * work.job_min_seconds(ctx["shape"], ctx["peak"],
                                        ctx["chips"])
    return 100.0 * least / res["window_s"]
