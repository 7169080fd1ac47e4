"""Standalone multi-host (DCN) dryrun — runnable without pytest.

Spawns a REAL 2-process `jax.distributed` cluster on localhost (4
virtual CPU devices per process → one global 8-device mesh) and runs,
in sequence: a cross-process psum MRTask, a full fused-scan GBM train,
a GLM IRLSM fit, and the member-drop fail-fast check. This is the
PROCESS-boundary path that a single-process virtual mesh cannot
exercise (SURVEY.md §2d
multi-host row; the round-2 DRF worker-crash class lives here).

Usage: python tools/dcn_dryrun.py   → prints one JSON line + exit 0/1.
"""

import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dcn_worker.py")
MODES = [("psum", (0, 0)), ("gbm", (0, 0)), ("glm", (0, 0)),
         ("drop", (0, 17))]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def run_mode(mode: str, want_rc) -> dict:
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    t0 = time.monotonic()
    procs = [subprocess.Popen(
        [sys.executable, WORKER, str(port), str(i), mode],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        text=True) for i in range(2)]
    outs, ok = [], True
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        return {"mode": mode, "ok": False, "error": "timeout",
                "tails": [o[-300:] for o in outs]}
    for i, (p, out) in enumerate(zip(procs, outs)):
        # drop mode: worker 1 dies on purpose and prints EXITING, not OK
        marker = "EXITING" if (mode == "drop" and i == 1) else "OK"
        if p.returncode != want_rc[i] or marker not in out:
            ok = False
    return {"mode": mode, "ok": ok,
            "seconds": round(time.monotonic() - t0, 1),
            **({} if ok else {"tails": [o[-300:] for o in outs]})}


def main() -> int:
    results = [run_mode(m, rc) for m, rc in MODES]
    ok = all(r["ok"] for r in results)
    print(json.dumps({"dcn_dryrun": "ok" if ok else "fail",
                      "processes": 2, "global_devices": 8,
                      "modes": results}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
