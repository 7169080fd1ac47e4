"""Record the small trace the reduction's tests read: one traced run of
a tiny copy of the one-chip cell, on the chip. Run once per toolchain
(`python3 bench/tests/record_trace.py <out dir>` through the chip tool);
the tests read the `.xplane.pb` kept beside them."""

import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)),
                os.path.dirname(HERE), HERE]

if __name__ == "__main__":
    import jax

    import shutil

    import rehearse
    import run
    import trace_reduce
    from registry import Registry

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    root = rehearse.tiny_root(tempfile.mkdtemp())
    load = trace_reduce.load

    def keep(path):
        os.makedirs(sys.argv[1], exist_ok=True)
        shutil.copy(path, sys.argv[1])
        return load(path)

    trace_reduce.load = keep       # the run deletes what it has read
    line = run.run_cell(Registry(root), "gbm-higgs.train", 7, 0.3, True,
                        devices[:1])
    print(json.dumps(line))
