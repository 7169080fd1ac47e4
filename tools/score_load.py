#!/usr/bin/env python
"""Closed-loop REST scoring load generator (docs/SERVING.md).

Hammers POST /3/Predictions/models/{key} (the inline serving route:
JSON rows in, predictions out, micro-batched server-side) with N
concurrent closed-loop workers — each worker keeps exactly one request
in flight, so offered load tracks service capacity, the way a fleet of
synchronous clients behaves.  Reports rows/s + latency percentiles as
ONE JSON line, plus the server's micro-batcher stats when the server
runs in-process.

Usage::

    python tools/score_load.py                      # self-contained:
        # starts an in-process REST server with a synthetic GBM
    python tools/score_load.py --url http://host:54321 --model gbm1
    python tools/score_load.py --concurrency 16 --rows 32 --seconds 10
    python tools/score_load.py --contributions    # TreeSHAP explain
        # route (POST .../contributions) under the same closed loop
    python tools/score_load.py \
        --url http://h1:54321,http://h2:54321 --model pool \
        --columns x0,...  --assert-zero-5xx      # drive a scorer POOL

Multi-target mode (a comma list of ``--url`` targets, or a dynamic
target provider via :func:`run_load_multi`) is the Service analog the
operator drills ride: a background poller tracks each target's
``/readyz`` and workers round-robin over the READY set only — a
replica mid-warm-up or cordoned for a rolling update receives nothing,
like a pod pulled from a Service's endpoints. ``--assert-zero-5xx``
makes the run fail loudly (rc 1) on ANY 5xx response — the
rolling-update acceptance bar (docs/OPERATOR.md).

This is the REST-level closed-loop view of the serving fast path
(request coalescing included). No benchmark cell times serving yet
(PERF.md §7 row 4).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _post_json(url: str, payload: dict, timeout: float = 120.0,
               headers: dict | None = None) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), method="POST",
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get_json(url: str, timeout: float = 5.0) -> dict | None:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return json.loads(r.read())
    except Exception:  # noqa: BLE001 — scrape is best-effort
        return None


def _self_server(port: int = 0):
    """Start an in-process server + synthetic GBM; returns
    (server, base_url, model_key, feature_columns, row_maker)."""
    import socket

    import numpy as np

    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu import rest
    from h2o_kubernetes_tpu.models import GBM
    from h2o_kubernetes_tpu.runtime import make_mesh, set_global_mesh

    set_global_mesh(make_mesh())
    rng = np.random.default_rng(0)
    n = 20_000
    cols = {f"x{i}": rng.normal(size=n).astype(np.float32)
            for i in range(8)}
    cols["c1"] = np.array(["a", "b", "c", "d"])[rng.integers(0, 4, n)]
    cols["y"] = np.where(cols["x0"] - cols["x1"] > 0, "late", "ontime")
    fr = h2o.Frame.from_arrays(cols)
    model = GBM(ntrees=20, max_depth=5, learn_rate=0.2, seed=1).train(
        y="y", training_frame=fr)
    rest.MODELS["score_load_gbm"] = model
    if port == 0:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
    srv = rest.start_server(port)
    return (srv, f"http://127.0.0.1:{port}", "score_load_gbm",
            [f"x{i}" for i in range(8)] + ["c1"])


def _percentile_ms(lat: list[float], p: float):
    """Index-pick percentile in ms over raw seconds latencies (None
    when empty) — the ONE percentile formula every load mode uses."""
    if not lat:
        return None
    lat = sorted(lat)
    return round(lat[min(len(lat) - 1, int(p * len(lat)))] * 1e3, 2)


def _result_record(latencies: list[float], wall: float,
                   rows_per_request: int, concurrency: int,
                   fivexx: list[str], errors: list[str],
                   **extra) -> dict:
    """The one result-record shape shared by every load mode — a new
    field lands in single-target AND multi-target AND zipf output or
    none of them."""
    n = len(latencies)
    return {
        "metric": "rest_score_rows_per_sec",
        "value": round(n * rows_per_request / max(wall, 1e-9), 1),
        "unit": "rows/s",
        "requests": n,
        "requests_per_s": round(n / max(wall, 1e-9), 1),
        "fivexx": len(fivexx),
        "fivexx_sample": fivexx[:5],
        "errors": len(errors),
        "error_sample": errors[:3],
        "p50_ms": _percentile_ms(latencies, 0.50),
        "p95_ms": _percentile_ms(latencies, 0.95),
        "p99_ms": _percentile_ms(latencies, 0.99),
        "concurrency": concurrency,
        "rows_per_request": rows_per_request,
        "seconds": round(wall, 2),
        **extra,
    }


def run_load(url: str, model_key: str, columns: list[str],
             concurrency: int = 8, rows_per_request: int = 32,
             seconds: float = 10.0, seed: int = 0,
             contributions: bool = False) -> dict:
    """Closed-loop drive; returns the result record (also printable).

    ``contributions=True`` drives the explainable-serving route
    (``POST .../contributions`` — per-row TreeSHAP through the same
    micro-batcher, docs/SERVING.md "Explainable serving") instead of
    predictions; success = a [rows, F+1] contributions matrix back."""
    suffix = "/contributions" if contributions else ""
    route = f"{url}/3/Predictions/models/{model_key}{suffix}"
    out_key = "contributions" if contributions else "predict"
    bodies = _make_bodies(columns, rows_per_request, seed)
    deadline = time.perf_counter() + seconds
    lock = threading.Lock()
    latencies: list[float] = []
    errors: list[str] = []
    fivexx: list[str] = []

    def worker(wid: int) -> None:
        import urllib.error

        i = wid
        while time.perf_counter() < deadline:
            body = bodies[i % len(bodies)]
            i += 1
            t0 = time.perf_counter()
            try:
                out = _post_json(route, body)
                ok = len(out[out_key]) == rows_per_request
            except urllib.error.HTTPError as e:
                # 5xx tracked apart from transport noise so
                # --assert-zero-5xx has a precise needle
                label = f"HTTP {e.code} {e.read()[:120]!r}"
                with lock:
                    (fivexx if e.code >= 500 else errors).append(label)
                continue
            except Exception as e:  # noqa: BLE001 — record, keep going
                with lock:
                    errors.append(repr(e)[:200])
                continue
            dt = time.perf_counter() - t0
            with lock:
                if ok:
                    latencies.append(dt)
                else:
                    errors.append("short response")

    # one warm-up request so the timed window measures steady state,
    # not the first XLA compile
    _post_json(route, bodies[0])
    t_start = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    return _result_record(latencies, wall, rows_per_request,
                          concurrency, fivexx, errors,
                          route="contributions" if contributions
                          else "predictions")


def _make_bodies(columns: list[str], rows_per_request: int, seed: int,
                 pool: int = 16) -> list[dict]:
    """Pre-generated list-shaped request bodies (shared by both load
    modes so workers spend their loop on HTTP, not JSON building)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    bodies = []
    for _ in range(pool):
        rows = [[(float(rng.normal()) if c != "c1" else
                  ["a", "b", "c", "d"][int(rng.integers(0, 4))])
                 for c in columns] for _ in range(rows_per_request)]
        bodies.append({"rows": rows, "columns": columns})
    return bodies


def run_load_multi(targets, model_key: str, columns: list[str],
                   concurrency: int = 4, rows_per_request: int = 8,
                   seconds: float | None = None, stop_event=None,
                   seed: int = 0, ready_poll_s: float = 0.05,
                   request_timeout: float = 30.0) -> dict:
    """Round-robin closed-loop drive over a DYNAMIC set of pool
    replicas — the k8s-Service analog for operator drills.

    ``targets`` is a list of base URLs or a zero-arg callable returning
    one (the reconciler's live endpoint list: replicas join as they
    are provisioned, leave the instant they are cordoned). A poller
    thread refreshes each target's ``/readyz`` every ``ready_poll_s``
    and workers pick targets FROM the ready set under its lock, so the
    generator never *chooses* an unready target by construction. (The
    pick→arrival in-flight race is the router race the operator's
    deregister grace exists for; the measured check of that contract
    is the SERVER-side ``scored_while_unready`` counter on /3/Stats,
    which the drills assert — not a client-side literal.) ``fivexx``
    counts real 5xx contract violations.

    Runs until ``stop_event`` is set (or ``seconds`` elapses). Returns
    the single-target record plus ``fivexx``/``fourxx``/``by_target``/
    ``no_ready_target_waits``."""
    import urllib.error

    get_targets = targets if callable(targets) else (lambda: targets)
    stop = stop_event or threading.Event()
    deadline = (time.perf_counter() + seconds) if seconds else None
    bodies = _make_bodies(columns, rows_per_request, seed)
    lock = threading.Lock()
    ready: set[str] = set()
    latencies: list[float] = []
    fivexx: list[str] = []
    fourxx: list[str] = []
    errors: list[str] = []
    by_target: dict[str, dict] = {}
    no_ready_waits = [0]

    def _done() -> bool:
        return stop.is_set() or \
            (deadline is not None and time.perf_counter() >= deadline)

    def poller():
        while not _done():
            now_ready = set()
            for t in list(get_targets()):
                try:
                    with urllib.request.urlopen(
                            t.rstrip("/") + "/readyz", timeout=2.0) as r:
                        if r.status == 200:
                            now_ready.add(t.rstrip("/"))
                except Exception:  # noqa: BLE001 — down/503 = unready
                    pass
            with lock:
                ready.clear()
                ready.update(now_ready)
            time.sleep(ready_poll_s)

    rr = [0]

    def worker(wid: int) -> None:
        i = wid
        while not _done():
            with lock:
                pool = sorted(ready)
                if pool:
                    target = pool[rr[0] % len(pool)]
                    rr[0] += 1
                else:
                    no_ready_waits[0] += 1   # under lock: workers race
            if not pool:
                time.sleep(0.02)
                continue
            body = bodies[i % len(bodies)]
            i += 1
            route = f"{target}/3/Predictions/models/{model_key}"
            t0 = time.perf_counter()
            try:
                out = _post_json(route, body, timeout=request_timeout)
                ok = len(out["predict"]) == rows_per_request
                dt = time.perf_counter() - t0
                with lock:
                    rec = by_target.setdefault(
                        target, {"requests": 0, "fivexx": 0})
                    rec["requests"] += 1
                    if ok:
                        latencies.append(dt)
                    else:
                        errors.append(f"{target}: short response")
            except urllib.error.HTTPError as e:
                label = f"{target}: HTTP {e.code} {e.read()[:120]!r}"
                with lock:
                    rec = by_target.setdefault(
                        target, {"requests": 0, "fivexx": 0})
                    rec["requests"] += 1
                    if e.code >= 500:
                        rec["fivexx"] += 1
                        fivexx.append(label)
                    else:
                        fourxx.append(label)
            except Exception as e:  # noqa: BLE001 — record, keep going
                with lock:
                    errors.append(f"{target}: {e!r}"[:200])

    t_start = time.perf_counter()
    pt = threading.Thread(target=poller, daemon=True,
                          name="score-load-ready-poller")
    pt.start()
    workers = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(concurrency)]
    for t in workers:
        t.start()
    for t in workers:
        t.join()
    pt.join(timeout=5.0)
    wall = time.perf_counter() - t_start
    return _result_record(latencies, wall, rows_per_request,
                          concurrency, fivexx, errors,
                          fourxx=len(fourxx),
                          no_ready_target_waits=no_ready_waits[0],
                          by_target=by_target)


# ---------------------------------------------------------------------------
# Multi-tenant Zipf traffic (docs/SERVING.md "Multi-tenant serving")
# ---------------------------------------------------------------------------
#
# ``--models N --zipf-s S`` drives N registry-pushed models with
# Zipf(s) popularity (rank 1 hottest) — the tenant-population shape a
# fleet node actually serves.  Per-model latency/5xx/shed accounting
# rides the same body-pool / result-record plumbing as the pool modes,
# plus popularity-DECILE percentiles (the tail decile is the fairness
# contract's needle) and a /3/Stats scrape of the byte-budgeted scorer
# cache (resident bytes vs budget, evictions, promotions, compile
# watch).


def _self_server_tenants(n_models: int, seed: int = 0,
                         base_variants: int = 4,
                         warm_buckets=(128,), port: int = 0):
    """In-process REST server with ``n_models`` registry-loaded tiny
    FlatTreeScorers under keys m000..m{N-1}; returns
    (server, url, model_keys, feature_columns).

    A handful of distinct base GBMs rotate across the tenant keys:
    every tenant is its OWN model instance (own jitted executables,
    own byte charge) while the artifact variety keeps warm-up cost
    bounded — same-HLO tenants warm from the persistent XLA cache."""
    import socket

    import numpy as np

    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu import rest
    from h2o_kubernetes_tpu.models import GBM
    from h2o_kubernetes_tpu.operator.registry import ModelRegistry
    from h2o_kubernetes_tpu.runtime import make_mesh, set_global_mesh
    from h2o_kubernetes_tpu.runtime.backend import \
        enable_persistent_compile_cache

    # every serving compile must persist (threshold 0): the
    # evict→promote contract under a byte budget is "a pcache hit,
    # never a cold compile", and tenant models compile in << 0.5s
    enable_persistent_compile_cache(min_compile_secs=0.0)
    set_global_mesh(make_mesh())
    rng = np.random.default_rng(seed)
    n = 2000
    cols = {f"x{i}": rng.normal(size=n).astype(np.float32)
            for i in range(6)}
    cols["y"] = np.where(cols["x0"] - cols["x1"] > 0, "late", "ontime")
    fr = h2o.Frame.from_arrays(cols)
    reg = ModelRegistry(f"mem://score_load_tenants_{os.getpid()}")
    nb = max(1, min(base_variants, n_models))
    arts = []
    for b in range(nb):
        m = GBM(ntrees=2 + b, max_depth=2, seed=b + 1).train(
            y="y", training_frame=fr)
        reg.publish(m, f"tenant{b}")
        arts.append(f"tenant{b}")
    if port == 0:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
    srv = rest.start_server(port)
    url = f"http://127.0.0.1:{port}"
    keys = [f"m{i:03d}" for i in range(n_models)]
    for i, key in enumerate(keys):
        reg.push(url, arts[i % nb], 1, key,
                 warm_buckets=list(warm_buckets))
    return srv, url, keys, [f"x{i}" for i in range(6)]


def _popularity_deciles(model_keys: list[str],
                        per_model: dict) -> list[dict]:
    """Aggregate per-model records into 10 popularity-rank deciles
    (decile 1 = hottest ranks). The TAIL decile's p99 is the fairness
    acceptance needle: it must hold its SLO while a hot decile
    floods."""
    N = len(model_keys)
    out = []
    for d in range(10):
        lo, hi = (d * N) // 10, ((d + 1) * N) // 10
        ks = model_keys[lo:hi]
        if not ks:
            continue
        lats = [t for k in ks for t in per_model[k]["lat"]]
        out.append({
            "decile": d + 1,
            "models": len(ks),
            "requests": sum(per_model[k]["requests"] for k in ks),
            "fivexx": sum(per_model[k]["fivexx"] for k in ks),
            "shed": sum(per_model[k]["shed"] for k in ks),
            "degraded": sum(per_model[k].get("degraded", 0)
                            for k in ks),
            "p50_ms": _percentile_ms(lats, 0.50),
            "p99_ms": _percentile_ms(lats, 0.99),
        })
    return out


def run_load_zipf(targets, model_keys: list[str], columns: list[str],
                  concurrency: int = 8, rows_per_request: int = 16,
                  seconds: float = 15.0, zipf_s: float = 1.1,
                  seed: int = 0, stop_event=None,
                  request_timeout: float = 30.0,
                  stats_poll_s: float = 0.5,
                  router: bool = False) -> dict:
    """Closed-loop Zipf(s) model-popularity drive: each request picks
    its model by popularity rank (key order = rank, 1 hottest) and
    round-robins over the READY targets, exactly like the pool mode.

    Returns the shared result record plus ``by_model`` (per-tenant
    requests/latency/5xx/shed), popularity ``deciles``, and a
    ``residency`` section sampled off /3/Stats every ``stats_poll_s``
    (max resident bytes observed, whether the byte budget was ever
    exceeded, eviction/promotion/compile deltas over the run).

    ``router=True`` is the sharded-fleet mode (the target is a
    front-door router, tools/chaos.py ``router-shard-kill``): a typed
    503 carrying the ``placement_pending`` hint is counted per model
    as ``degraded`` — the EXPECTED answer for a tail tenant whose only
    shard just died, mid re-placement — instead of a raw 5xx, so the
    zero-5xx acceptance needle stays precise."""
    import urllib.error

    import numpy as np

    from tools.datasets import zipf_probs

    if isinstance(targets, str):
        targets = [targets]
    get_targets = targets if callable(targets) else (lambda: targets)
    probs = zipf_probs(len(model_keys), zipf_s)
    stop = stop_event or threading.Event()
    deadline = time.perf_counter() + seconds
    bodies = _make_bodies(columns, rows_per_request, seed)
    lock = threading.Lock()
    ready: set[str] = set()
    latencies: list[float] = []
    fivexx: list[str] = []
    errors: list[str] = []
    per_model = {k: {"requests": 0, "fivexx": 0, "shed": 0,
                     "fourxx": 0, "degraded": 0, "lat": []}
                 for k in model_keys}
    residency = {"samples": 0, "max_resident_bytes": 0,
                 "budget_bytes": None, "budget_exceeded": 0,
                 "max_resident_models": 0}
    stats_first: dict[str, dict] = {}   # per TARGET: deltas must not
    stats_last: dict[str, dict] = {}    # mix one replica into another
    target_failovers = [0]    # router mode: transport-level re-sends

    def _done() -> bool:
        return stop.is_set() or time.perf_counter() >= deadline

    def poller():
        while not _done():
            now_ready = set()
            for t in list(get_targets()):
                st = _get_json(t.rstrip("/") + "/readyz", timeout=2.0)
                if st is not None:
                    now_ready.add(t.rstrip("/"))
            with lock:
                ready.clear()
                ready.update(now_ready)
            # residency watch: the budget contract is "never exceeded
            # WHILE the storm runs", so it is sampled live, not once
            # at the end
            for t in sorted(now_ready):
                st = _get_json(t + "/3/Stats", timeout=2.0)
                if not st:
                    continue
                sc = st.get("scorer_cache") or {}
                with lock:
                    stats_first.setdefault(t, st)
                    stats_last[t] = st
                    residency["samples"] += 1
                    rb = int(sc.get("resident_bytes") or 0)
                    bb = int(sc.get("budget_bytes") or 0)
                    residency["max_resident_bytes"] = max(
                        residency["max_resident_bytes"], rb)
                    residency["max_resident_models"] = max(
                        residency["max_resident_models"],
                        int(sc.get("resident") or 0))
                    residency["budget_bytes"] = bb
                    if bb > 0 and rb > bb:
                        residency["budget_exceeded"] += 1
            time.sleep(stats_poll_s)

    rr = [0]

    def worker(wid: int) -> None:
        rng = np.random.default_rng(seed * 1000 + wid + 1)
        i = wid
        while not _done():
            with lock:
                pool = sorted(ready)
                if pool:
                    target = pool[rr[0] % len(pool)]
                    rr[0] += 1
            if not pool:
                time.sleep(0.02)
                continue
            key = model_keys[int(rng.choice(len(model_keys), p=probs))]
            body = bodies[i % len(bodies)]
            i += 1
            # router mode: a killed router's in-flight requests die at
            # the TRANSPORT level (reset/refused) — exactly the
            # failure N interchangeable routers behind a balancer
            # exist to absorb, so the same request retries on each
            # remaining ready target before anything lands in
            # `errors` (a balancer re-dispatches the same way)
            with lock:
                alts = [t for t in sorted(ready) if t != target]
            tries = [target] + (alts if router else [])
            for ti, tgt in enumerate(tries):
                route = f"{tgt}/3/Predictions/models/{key}"
                t0 = time.perf_counter()
                try:
                    out = _post_json(route, body,
                                     timeout=request_timeout)
                    ok = len(out["predict"]) == rows_per_request
                    dt = time.perf_counter() - t0
                    with lock:
                        rec = per_model[key]
                        rec["requests"] += 1
                        if ok:
                            rec["lat"].append(dt)
                            latencies.append(dt)
                        else:
                            errors.append(f"{key}: short response")
                    break
                except urllib.error.HTTPError as e:
                    ebody = e.read()
                    label = f"{key}: HTTP {e.code} {ebody[:120]!r}"
                    degraded = (router and e.code == 503
                                and (b"placement_pending" in ebody
                                     or b"table_pending" in ebody))
                    with lock:
                        rec = per_model[key]
                        rec["requests"] += 1
                        if degraded:
                            # the router's typed degraded answer: the
                            # tenant's shard is down and re-placement
                            # is in flight — expected during the
                            # drill's failure window, not a 5xx
                            # contract breach
                            rec["degraded"] += 1
                        elif e.code >= 500:
                            rec["fivexx"] += 1
                            fivexx.append(label)
                        elif e.code == 429:
                            rec["shed"] += 1
                        else:
                            rec["fourxx"] += 1
                            errors.append(label[:200])
                    if e.code == 429 or degraded:
                        time.sleep(0.005)   # shed: backoff, retry on
                    break
                except Exception as e:  # noqa: BLE001 — failover/record
                    with lock:
                        ready.discard(tgt.rstrip("/"))
                    if ti + 1 < len(tries):
                        with lock:
                            target_failovers[0] += 1
                        continue
                    with lock:
                        errors.append(f"{key}: {e!r}"[:200])

    t_start = time.perf_counter()
    pt = threading.Thread(target=poller, daemon=True,
                          name="score-load-zipf-poller")
    pt.start()
    workers = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(concurrency)]
    for t in workers:
        t.start()
    for t in workers:
        t.join()
    pt.join(timeout=5.0)
    wall = time.perf_counter() - t_start

    def _delta(section: str, field: str):
        tot, seen = 0, False
        for t, st0 in stats_first.items():
            st1 = stats_last.get(t)
            if not st1:
                continue
            a = (st0.get(section) or {}).get(field)
            b = (st1.get(section) or {}).get(field)
            if a is None or b is None:
                continue
            tot += b - a
            seen = True
        return tot if seen else None

    residency["evictions_delta"] = _delta("scorer_cache", "evictions")
    residency["promotions_delta"] = _delta("scorer_cache",
                                           "promotions")
    residency["compiles_delta"] = _delta("compiles", "compiles")
    residency["pcache_hits_delta"] = _delta("compiles", "pcache_hits")
    residency["pcache_misses_delta"] = _delta("compiles",
                                              "pcache_misses")
    shed = sum(r["shed"] for r in per_model.values())
    return _result_record(
        latencies, wall, rows_per_request, concurrency, fivexx, errors,
        zipf_s=zipf_s, models=len(model_keys), shed=shed,
        degraded=sum(r["degraded"] for r in per_model.values()),
        target_failovers=target_failovers[0],
        by_model={k: {"requests": r["requests"],
                      "fivexx": r["fivexx"], "shed": r["shed"],
                      "degraded": r["degraded"],
                      "p50_ms": _percentile_ms(r["lat"], 0.50),
                      "p99_ms": _percentile_ms(r["lat"], 0.99)}
                  for k, r in per_model.items()},
        deciles=_popularity_deciles(model_keys, per_model),
        residency=residency)


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--url", default=None,
                    help="server base URL, or a comma list of pool "
                    "replica URLs (round-robin multi-target mode); "
                    "omit to self-host")
    ap.add_argument("--model", default=None, help="model key to score")
    ap.add_argument("--columns", default=None,
                    help="comma list of feature columns (remote mode)")
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--rows", type=int, default=32,
                    help="rows per request")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--models", type=int, default=0,
                    help="multi-tenant mode: drive N models under "
                    "Zipf popularity (self-host: N tiny registry-"
                    "pushed tenants m000..; with --url, keys "
                    "'{--model}{i:03d}' must already be loaded)")
    ap.add_argument("--zipf-s", type=float, default=1.1,
                    help="Zipf exponent for --models popularity "
                    "(rank 1 hottest; higher = hotter head)")
    ap.add_argument("--router", action="store_true",
                    help="with --models + --url: the target is a "
                    "sharded-fleet front-door router — typed 503s "
                    "with the placement_pending hint count as "
                    "'degraded' (expected while a dead shard's "
                    "tenants re-place), not as 5xx")
    ap.add_argument("--assert-zero-5xx", action="store_true",
                    help="fail (rc 1) if ANY response was a 5xx — the "
                    "rolling-update drill's acceptance bar")
    ap.add_argument("--contributions", action="store_true",
                    help="drive the explainable-serving route "
                    "(POST .../contributions, per-row TreeSHAP) "
                    "instead of predictions — single-target mode")
    args = ap.parse_args(argv)
    if args.contributions and (args.models > 0 or
                               (args.url and "," in args.url)):
        print("--contributions is a single-target mode (no --models / "
              "multi-URL)", file=sys.stderr)
        return 2

    srv = None
    multi = args.url is not None and "," in args.url
    if args.models > 0:
        # multi-tenant Zipf traffic mode
        if args.url is None:
            srv, url, keys, columns = _self_server_tenants(
                args.models, warm_buckets=(max(args.rows, 1),))
            targets = [url]
        else:
            if not args.model or not args.columns:
                print("--url + --models needs --model (key prefix) "
                      "and --columns", file=sys.stderr)
                return 2
            targets = [u.strip().rstrip("/")
                       for u in args.url.split(",") if u.strip()]
            keys = [f"{args.model}{i:03d}" for i in range(args.models)]
            columns = args.columns.split(",")
        try:
            out = run_load_zipf(targets, keys, columns,
                                concurrency=args.concurrency,
                                rows_per_request=args.rows,
                                seconds=args.seconds,
                                zipf_s=args.zipf_s,
                                router=args.router)
            print(json.dumps(out))
            if args.assert_zero_5xx and out.get("fivexx", 0) > 0:
                print(f"FAIL: {out['fivexx']} 5xx responses "
                      f"(sample: {out.get('fivexx_sample')})",
                      file=sys.stderr)
                return 1
            return 0 if out["errors"] == 0 and out["requests"] > 0 \
                and out.get("fivexx", 0) == 0 else 1
        finally:
            if srv is not None:
                srv.shutdown()
    if args.url is None:
        srv, url, model_key, columns = _self_server()
    else:
        url = args.url.rstrip(",")
        if not args.model or not args.columns:
            print("--url mode needs --model and --columns",
                  file=sys.stderr)
            return 2
        model_key, columns = args.model, args.columns.split(",")
    try:
        if multi:
            targets = [u.strip().rstrip("/")
                       for u in url.split(",") if u.strip()]
            out = run_load_multi(targets, model_key, columns,
                                 concurrency=args.concurrency,
                                 rows_per_request=args.rows,
                                 seconds=args.seconds)
        else:
            out = run_load(url.rstrip("/"), model_key, columns,
                           concurrency=args.concurrency,
                           rows_per_request=args.rows,
                           seconds=args.seconds,
                           contributions=args.contributions)
        if srv is not None:
            from h2o_kubernetes_tpu import rest

            out["batcher"] = dict(rest.BATCHER.stats)
        print(json.dumps(out))
        if args.assert_zero_5xx and out.get("fivexx", 0) > 0:
            print(f"FAIL: {out['fivexx']} 5xx responses "
                  f"(sample: {out.get('fivexx_sample')})",
                  file=sys.stderr)
            return 1
        return 0 if out["errors"] == 0 and out["requests"] > 0 \
            and out.get("fivexx", 0) == 0 else 1
    finally:
        if srv is not None:
            srv.shutdown()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
