"""Chunked test runner — the supported way to run the whole suite.

A monolithic ``pytest tests/`` on a small host can stall indefinitely:
XLA:CPU's collective rendezvous starves when many mesh tests share one
core with background load (tests/conftest.py documents the failure
mode; VERDICT r4 hit it live). Running module-by-module bounds each
rendezvous window and makes a hang attributable to a file. CI and the
round ritual both use this entry point.

Usage:
    python tools/run_tests.py           # fast tier (-m "not slow")
    python tools/run_tests.py --slow    # slow tier only
    python tools/run_tests.py --all     # both tiers
    python tools/run_tests.py --chaos   # chaos drill suite only
                                        # (tools/chaos.py all); combine
                                        # with --all/--slow to append it
    python tools/run_tests.py --timeout 1200   # per-module cap
    python tools/run_tests.py --tier1-sharded  # THE tier-1 verify:
                                        # fast tier, per-module
                                        # timeouts, aggregate
                                        # DOTS_PASSED=<n> + rc

``--tier1-sharded`` is the ROADMAP verify entry point: the monolithic
``pytest tests/`` command outgrew any single wall cap on a 1-core box
(rc 124 at ~76% with zero failures), so the verify now runs the same
fast tier sharded module-by-module — each module under its own
``--timeout`` — and aggregates the per-module pytest pass counts into
one ``DOTS_PASSED=<total>`` line and one exit code (0 only if every
module passed). Same tests, same markers; only the wall-cap
granularity changed.

A preflight scan warns (or, with ``--strict-preflight`` /
``H2O_TPU_PREFLIGHT_STRICT=1``, fails) when orphaned load-drill or
scorer-pod processes are still running on the box — a leftover
AutoML run once starved tier-1 into rendezvous stalls, and nothing
timed on a contended core is trustworthy.

Prints one status line per module and a final JSON summary; exit 0
only if every module passed.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# cmdline fragments that mark a load drill or a scorer pod: one such
# process left over from an earlier round starves the shared core and
# turns tier-1's collective rendezvous into timeouts (a stale AutoML
# run found at 72% CPU during the PR-4 round did exactly that —
# CHANGES.md PR 4 ops note)
_ORPHAN_PATTERNS = ("score_load", "operator.pod")

# operator scorer-pool pods are REAPED (SIGKILL), not just reported —
# but ONLY when their parent reconciler is gone (the pod has been
# reparented to init): a pod only exists as a child of a reconciler,
# so an orphaned one is unambiguously a wedged drill's leftover — a
# full JAX interpreter holding a port and a core, guaranteed to starve
# the tier-1 run that follows. A pod whose parent is still alive may
# belong to a drill or operator running concurrently on this box and
# is reported, never killed. The other patterns stay warn-only.
_REAP_PATTERNS = ("operator.pod",)


def _ppid(pid: int) -> int | None:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return int(f.read().split(")")[-1].split()[1])
    except (OSError, ValueError, IndexError):
        return None


def find_orphan_processes() -> list[tuple[int, str]]:
    """(pid, cmdline) of processes that look like leftover bench/AutoML
    workloads — excluding this process and its ancestors (running the
    suite FROM a bench wrapper must not flag itself)."""
    me = os.getpid()
    ancestors = set()
    pid = me
    for _ in range(32):                     # walk up to init
        try:
            with open(f"/proc/{pid}/stat") as f:
                ppid = int(f.read().split(")")[-1].split()[1])
        except (OSError, ValueError, IndexError):
            break
        ancestors.add(pid)
        if ppid <= 1:
            break
        pid = ppid
    out = []
    try:
        pids = [int(d) for d in os.listdir("/proc") if d.isdigit()]
    except OSError:
        return out                          # no procfs (macOS): skip
    for pid in pids:
        if pid == me or pid in ancestors:
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                argv = f.read().split(b"\0")
                cmd = b" ".join(argv).decode(errors="replace").strip()
        except OSError:
            continue
        # only interpreter processes count: 'vim tools/score_load.py' or a
        # grep mentioning the name is not a workload
        if not argv or b"python" not in argv[0].lower():
            continue
        if cmd and any(pat in cmd for pat in _ORPHAN_PATTERNS):
            out.append((pid, cmd[:160]))
    return out


def _adoptable_manifest(pid: int, cmd: str) -> str | None:
    """Path of a VALID adoption manifest on this pod's cmdline, else
    None. A parentless pod whose `--manifest` file exists and names
    this pid is ADOPTABLE — a restartable operator's data plane
    surviving its controller (docs/OPERATOR.md "Control-plane
    recovery"), not a leak. The reaper must report it, never kill it.
    A pod whose manifest is gone (drill workdir deleted) or lies
    about the pid is an ordinary leak and still gets reaped."""
    parts = cmd.split()
    try:
        path = parts[parts.index("--manifest") + 1]
    except (ValueError, IndexError):
        return None
    try:
        with open(path) as f:
            doc = json.load(f)
        return path if int(doc.get("pid", -1)) == pid else None
    except (OSError, ValueError):
        return None


def reap_orphan_pods(orphans: list[tuple[int, str]]
                     ) -> list[tuple[int, str]]:
    """SIGKILL orphaned scorer-pool pods — pods whose reconciler
    parent is gone (ppid reparented to init); see _REAP_PATTERNS.
    Returns the orphans still left to report: pods with a live parent
    (a concurrent drill/operator owns them), ADOPTABLE pods (live
    manifest — a restarted operator will inherit them) and anything
    that refuses to die, so a strict preflight still fails on them."""
    import signal

    remaining = []
    for pid, cmd in orphans:
        ppid = _ppid(pid)
        if not any(pat in cmd for pat in _REAP_PATTERNS) \
                or ppid is None or ppid > 1:
            remaining.append((pid, cmd))
            continue
        man = _adoptable_manifest(pid, cmd)
        if man is not None:
            print(f"[preflight] pod {pid} is parentless but "
                  f"ADOPTABLE (manifest {man}) — reporting, not "
                  f"killing: {cmd}", flush=True)
            remaining.append((pid, cmd))
            continue
        try:
            os.kill(pid, signal.SIGKILL)
            print(f"[preflight] reaped orphaned scorer-pool pod "
                  f"{pid} (parent gone): {cmd}", flush=True)
        except ProcessLookupError:
            pass                     # already gone
        except PermissionError:
            remaining.append((pid, cmd))
    return remaining


def preflight(strict: bool) -> bool:
    """Scan for orphaned bench/AutoML processes BEFORE timing anything;
    returns False (and prints the PIDs) when the box is not clean.
    Orphaned scorer-pool pods are reaped outright (a wedged drill's
    leftover must not starve the run); the rest warn by default and
    fail the run under --strict-preflight or
    H2O_TPU_PREFLIGHT_STRICT=1."""
    orphans = reap_orphan_pods(find_orphan_processes())
    if not orphans:
        return True
    print(f"[preflight] {len(orphans)} orphaned bench/automl "
          "process(es) are competing for this box — timings below "
          "are not trustworthy:", flush=True)
    for pid, cmd in orphans:
        print(f"[preflight]   pid {pid}: {cmd}", flush=True)
    if strict:
        print("[preflight] strict mode: refusing to run "
              "(kill the processes above or drop --strict-preflight)",
              flush=True)
        return False
    print("[preflight] continuing anyway (pass --strict-preflight to "
          "fail instead)", flush=True)
    return True


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slow", action="store_true",
                    help="run only the slow-marked tier")
    ap.add_argument("--all", action="store_true",
                    help="run both tiers (fast then slow)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the chaos drill suite (tools/chaos.py "
                    "all); alone it replaces the pytest tiers")
    ap.add_argument("--timeout", type=float, default=1500.0,
                    help="per-module wall cap (a starved rendezvous "
                    "hangs forever; this converts it into a named "
                    "module failure)")
    ap.add_argument("--strict-preflight", action="store_true",
                    help="fail (rc 2) when orphaned bench/automl "
                    "processes are found instead of warning")
    ap.add_argument("--tier1-sharded", action="store_true",
                    help="tier-1 verify mode: run the fast tier "
                    "module-by-module (each under its own --timeout) "
                    "and print an aggregate DOTS_PASSED=<n> line; "
                    "exit 0 only if every module passed")
    args = ap.parse_args()

    strict = args.strict_preflight or \
        os.environ.get("H2O_TPU_PREFLIGHT_STRICT") == "1"
    if not preflight(strict):
        return 2

    modules = sorted(glob.glob(os.path.join(REPO, "tests", "test_*.py")))
    tiers = (["not slow", "slow"] if args.all
             else ["slow"] if args.slow else ["not slow"])
    if args.tier1_sharded:
        tiers = ["not slow"]         # THE tier-1 verify tier
    if args.chaos and not (args.all or args.slow
                           or args.tier1_sharded):
        tiers = []                   # drills only
    results = []
    passed_total = 0
    t0 = time.monotonic()
    # per-test timing lines ([time] …, tests/conftest.py hook): on a
    # module TIMEOUT the partial output still carries every COMPLETED
    # test's duration, so the cap failure names the slow tests instead
    # of just the module
    env = dict(os.environ, H2O_TPU_TEST_TIMINGS="1")
    for tier in tiers:
        for mod in modules:
            name = os.path.basename(mod)
            cmd = [sys.executable, "-m", "pytest", mod, "-q",
                   "-m", tier, "--no-header", "-p", "no:cacheprovider"]
            start = time.monotonic()
            # own process group: on timeout kill the WHOLE group —
            # pytest's grandchildren (test_distributed's DCN workers)
            # would otherwise survive and starve every later module
            # into a cascade of timeouts
            proc = subprocess.Popen(cmd, cwd=REPO, env=env,
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE,
                                    start_new_session=True)
            try:
                out_b, err_b = proc.communicate(timeout=args.timeout)
                out = out_b.decode(errors="replace")
                if not out.strip():
                    # collection/usage errors (rc 2-4) print to stderr
                    out = err_b.decode(errors="replace")
                tail = out.strip().splitlines()[-1] if out.strip() else ""
                # rc 5 = no tests collected for this -m filter
                status = "ok" if proc.returncode == 0 else \
                    "none" if proc.returncode == 5 else "FAIL"
            except subprocess.TimeoutExpired as e:
                import signal

                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
                out_b, _ = proc.communicate()
                partial = (e.stdout or out_b or b"").decode(
                    errors="replace")
                status = "TIMEOUT"
                tail = partial.strip().splitlines()[-1] \
                    if partial.strip() else ""
                # keep the per-module cap honest: name the slowest 5
                # COMPLETED tests (and by elimination, the stuck one is
                # whatever started after the last [time] line)
                times = []
                for ln in partial.splitlines():
                    if ln.startswith("[time] "):
                        parts = ln.split(maxsplit=2)
                        try:
                            times.append((float(parts[1].rstrip("s")),
                                          parts[2]))
                        except (IndexError, ValueError):
                            pass
                for secs, node in sorted(times, reverse=True)[:5]:
                    print(f"    [slow] {secs:8.2f}s {node}", flush=True)
            dt = time.monotonic() - start
            # pytest -q summary tail ("30 passed, 1 warning in 27.7s")
            # → per-module pass count, aggregated into DOTS_PASSED for
            # --tier1-sharded (the sharded analog of counting dots)
            m = re.search(r"(\d+) passed", tail)
            mod_passed = int(m.group(1)) if m else 0
            passed_total += mod_passed
            results.append({"module": name, "tier": tier,
                            "status": status, "seconds": round(dt, 1),
                            "passed": mod_passed,
                            "tail": tail[-120:]})
            print(f"[{status:>7}] {name:<32} ({tier}) {dt:6.1f}s "
                  f"{tail[-80:]}", flush=True)

    if args.chaos:
        # the drill suite is one subprocess, same timeout discipline as
        # a test module (a wedged drain must become a named failure)
        cmd = [sys.executable, os.path.join(REPO, "tools", "chaos.py"),
               "all"]
        start = time.monotonic()
        # own process group, like the module loop above: on timeout the
        # drill's grandchildren (drain-under-load's pod subprocess — a
        # full JAX interpreter with a REST server) must die with it
        proc = subprocess.Popen(cmd, cwd=REPO, env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE,
                                start_new_session=True)
        try:
            out_b, err_b = proc.communicate(timeout=args.timeout)
            out = (out_b + err_b).decode(errors="replace")
            status = "ok" if proc.returncode == 0 else "FAIL"
        except subprocess.TimeoutExpired as e:
            import signal

            try:
                os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            out_b, err_b = proc.communicate()
            out = ((e.stdout or out_b or b"")
                   + (e.stderr or err_b or b"")).decode(errors="replace")
            status = "TIMEOUT"
        dt = time.monotonic() - start
        tail = out.strip().splitlines()[-1] if out.strip() else ""
        results.append({"module": "chaos.py all", "tier": "chaos",
                        "status": status, "seconds": round(dt, 1),
                        "tail": tail[-120:]})
        print(f"[{status:>7}] {'chaos.py all':<32} (chaos) {dt:6.1f}s "
              f"{tail[-80:]}", flush=True)

    failed = [r for r in results if r["status"] in ("FAIL", "TIMEOUT")]
    summary = {
        "run_tests": "pass" if not failed else "fail",
        "modules": len(results),
        "failed": [r["module"] for r in failed],
        "wall_seconds": round(time.monotonic() - t0, 1)}
    if args.tier1_sharded:
        summary["passed"] = passed_total
        # same grep-able shape as the old monolithic verify line, so
        # round tooling keeps one regex across both eras
        print(f"DOTS_PASSED={passed_total}", flush=True)
    print(json.dumps(summary))
    return 0 if not failed else 1


if __name__ == "__main__":
    sys.exit(main())
