"""Fleet telemetry (ISSUE 14 tentpole): ONE process-wide metrics
registry behind every stats surface, Prometheus exposition everywhere,
end-to-end request tracing.

Contracts pinned here:

- the registry is exact under thread fire (N threads x M increments
  across counters/histograms -> exact totals, no lost updates);
- tenant-label cardinality is BOUNDED: 1000 distinct model labels
  produce at most top-K + 1 (`other`) series, with the rollup
  conserving the total;
- `/3/Stats` keeps its byte-shape-compatible JSON (golden key-shape
  test) while being assembled from the registry snapshot, plus the
  sanctioned `build` block;
- every counter `/3/Stats` reports appears on `GET /metrics` under the
  shared naming rule (inventory-diff test — the two surfaces cannot
  drift);
- a traced request decomposes into admission/queue/assemble/dispatch/
  total spans at `GET /3/Trace/{id}` and echoes its X-H2O-Trace-Id;
- a LOST router hedge never double-counts the tenant's forwarded
  counter, and every fired hedge settles to exactly one of
  won/lost/cancelled on the hedge shard.
"""

import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import h2o_kubernetes_tpu as h2o
from h2o_kubernetes_tpu import rest
from h2o_kubernetes_tpu.models import GBM
from h2o_kubernetes_tpu.operator.router import start_router
from h2o_kubernetes_tpu.runtime import telemetry
from h2o_kubernetes_tpu.runtime.telemetry import (
    ALLOWED_LABELS, REGISTRY, MetricsRegistry, build_info,
    metric_name, parse_prometheus_text)

pytestmark = pytest.mark.chaos


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------


def test_label_allowlist_enforced():
    r = MetricsRegistry()
    with pytest.raises(ValueError, match="allowlist"):
        r.counter("h2o_bad_total", "x", label="tenant_name")
    # allowed labels pass
    for lab in ("model", "shard", "phase"):
        assert lab in ALLOWED_LABELS
        r.counter(f"h2o_ok_{lab}_total", "x", label=lab)


def test_registry_hammer_no_lost_updates():
    """N threads x M increments across counters + a histogram ->
    exact totals. A lost update would silently corrupt autoscale
    signals fleet-wide, so this is the registry's core contract."""
    r = MetricsRegistry()
    c_plain = r.counter("h2o_plain_total", "")
    c_model = r.counter("h2o_bymodel_total", "", label="model")
    g = r.gauge("h2o_gauge", "")
    h = r.histogram("h2o_lat_seconds", "", label="phase")
    threads, per = 8, 5000
    errs = []

    def work(tid):
        try:
            for i in range(per):
                c_plain.inc()
                c_model.inc(label_value=f"m{i % 30}")
                h.observe(0.001 * (i % 7), label_value="total")
                g.set(float(tid))
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=work, args=(t,))
          for t in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    assert c_plain.value() == threads * per
    assert sum(v for _, _, v in c_model.samples()) == threads * per
    snap = h.snapshot("total")
    assert snap["count"] == threads * per


def test_model_label_cardinality_bounded():
    """1000 distinct model labels -> at most top-K + 1 series, the
    rollup conserves the total, and the hot labels keep their own
    series."""
    r = MetricsRegistry()
    c = r.counter("h2o_req_total", "", label="model")
    k = telemetry._topk()
    # hot tenants first (real traffic rank), then the long tail
    for hot in range(5):
        for _ in range(200):
            c.inc(label_value=f"hot{hot}")
    for i in range(1000):
        c.inc(label_value=f"tail{i:04d}")
    assert c.series_count() <= k + 1
    samples = {tuple(sorted(lbl.items())): v
               for _, lbl, v in c.samples()}
    total = sum(samples.values())
    assert total == 5 * 200 + 1000          # nothing lost to the cap
    for hot in range(5):                     # hot series survive
        assert ((("model", f"hot{hot}"),)) in samples
    assert samples.get((("model", "other"),), 0) > 0


def test_histogram_buckets_and_quantile():
    r = MetricsRegistry()
    h = r.histogram("h2o_x_seconds", "", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["buckets"][0.01] == 1
    assert snap["buckets"][0.1] == 3
    assert snap["buckets"][1.0] == 4
    q50 = h.quantile(0.5)
    assert 0.01 <= q50 <= 0.1
    # exposition carries cumulative buckets + +Inf + sum + count
    text = r.prometheus_text()
    p = parse_prometheus_text(text)
    assert p[("h2o_x_seconds_bucket", (("le", "0.1"),))] == 3
    assert p[("h2o_x_seconds_bucket", (("le", "+Inf"),))] == 5
    assert p[("h2o_x_seconds_count", ())] == 5


def test_prometheus_text_roundtrip_and_groups():
    r = MetricsRegistry()
    r.counter("h2o_a_total", "help a").inc(3)
    r.register_group("grp", lambda: {
        "n": 7, "flag": True, "state": "open",
        "nested": {"x": 1.5}, "skipped": [1, 2]})
    r.register_group("per_model", lambda: {
        "m1": {"requests": 4}, "m2": {"requests": 2}},
        labeled="model")
    p = parse_prometheus_text(r.prometheus_text())
    assert p[("h2o_a_total", ())] == 3
    assert p[(metric_name("grp", "n"), ())] == 7
    assert p[(metric_name("grp", "flag"), ())] == 1
    assert p[(metric_name("grp", "state"), (("value", "open"),))] == 1
    assert p[(metric_name("grp", "nested", "x"), ())] == 1.5
    assert p[(metric_name("per_model", "requests"),
              (("model", "m1"),))] == 4
    assert p[(metric_name("per_model", "requests"),
              (("model", "m2"),))] == 2


def test_labeled_group_topk_rollup():
    """The scrape-time top-K + `other` rollup for labeled groups:
    1000 tenants on /3/Stats expose <= K + 1 series per counter on
    /metrics, hottest kept, mass conserved."""
    r = MetricsRegistry()
    k = telemetry._topk()
    data = {f"t{i:04d}": {"requests": i} for i in range(1000)}
    r.register_group("models", lambda: data, labeled="model")
    p = parse_prometheus_text(r.prometheus_text())
    series = [(lbls, v) for (n, lbls), v in p.items()
              if n == metric_name("models", "requests")]
    assert len(series) <= k + 1
    assert sum(v for _, v in series) == sum(i for i in range(1000))
    labels = {dict(lbls)["model"] for lbls, _ in series}
    assert "t0999" in labels            # hottest kept by traffic
    assert "other" in labels


def test_group_registration_idempotent():
    r = MetricsRegistry()
    r.register_group("g", lambda: {"v": 1})
    r.register_group("g", lambda: {"v": 2})     # last wins
    assert r.group_snapshot()["g"] == {"v": 2}
    # a raising group yields an error marker, never a dead scrape
    r.register_group("boom", lambda: 1 / 0)
    snap = r.group_snapshot()
    assert "error" in snap["boom"]
    assert snap["g"] == {"v": 2}


def test_trace_id_sanitize():
    assert telemetry.trace_id_from({"X-H2O-Trace-Id": "ab-C_9"}) \
        == "ab-C_9"
    # header injection / garbage mints a fresh id instead
    bad = telemetry.trace_id_from(
        {"X-H2O-Trace-Id": 'x"\r\nSet-Cookie: p'})
    assert bad and all(c.isalnum() or c in "-_" for c in bad)
    assert telemetry.trace_id_from({})


def test_phase_span_feeds_histogram_and_timeline():
    from h2o_kubernetes_tpu.diagnostics import timeline

    hist = telemetry.train_phase_histogram()
    before = hist.snapshot("unit_test_phase")["count"]
    with telemetry.phase_span("unit_test_phase"):
        time.sleep(0.002)
    assert hist.snapshot("unit_test_phase")["count"] == before + 1
    evs = [e for e in timeline.events("phase")
           if e.get("phase") == "unit_test_phase"]
    assert evs and evs[-1]["dur_ms"] >= 1.0


def test_build_info_fields():
    b = build_info()
    assert b["version"]
    assert b["pid"]
    assert b["uptime_s"] >= 0
    assert b["hostfp"]
    # jax versions come from package metadata, never an import
    assert "jax" in b and "jaxlib" in b


def test_status_listener_serves_metrics():
    srv = telemetry.start_status_listener(0, extra_groups=lambda: {
        "operator": {"pool": "p", "n": 3}})
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            p = parse_prometheus_text(r.read().decode())
        assert p[(metric_name("operator", "n"), ())] == 3
        assert any(k[0] == "h2o_build_info" for k in p)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            hz = json.loads(r.read())
        assert hz["alive"] and hz["build"]["pid"]
    finally:
        srv.shutdown()
        srv.server_close()


# ---------------------------------------------------------------------------
# REST surface: golden shape, inventory diff, request tracing
# ---------------------------------------------------------------------------


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(base, path):
    try:
        with urllib.request.urlopen(base + path, timeout=30) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _post(base, path, payload, headers=None):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(), method="POST",
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _train_tiny(seed=5):
    rng = np.random.default_rng(seed)
    n = 300
    cols = {f"x{i}": rng.normal(size=n).astype(np.float32)
            for i in range(4)}
    cols["y"] = np.where(cols["x0"] - cols["x1"] > 0, "late", "ontime")
    fr = h2o.Frame.from_arrays(cols)
    return GBM(ntrees=2, max_depth=2, seed=seed).train(
        y="y", training_frame=fr)


@pytest.fixture(scope="module")
def stats_server(mesh8):
    # module-scoped: one GBM train + one server for the three REST
    # surface tests below (they only READ /3/Stats//metrics or add
    # traffic, which every assertion tolerates)
    port = _free_port()
    rest.MODELS["telem_pm"] = _train_tiny()
    srv = rest.start_server(port)
    yield f"http://127.0.0.1:{port}"
    srv.shutdown()
    rest.MODELS.pop("telem_pm", None)
    rest.READINESS_GATES.clear()
    with rest._STATS_LOCK:
        rest.MODEL_STATS.pop("telem_pm", None)


def _shape(obj):
    """Recursive key-shape of a JSON payload (dict keys only — values
    and list contents are data, not shape)."""
    if isinstance(obj, dict):
        return {k: _shape(v) for k, v in sorted(obj.items())}
    return type(obj).__name__


# The golden /3/Stats key-shape: the PRE-registry sections verbatim
# (ready/reasons + lifecycle spread + identity/scorer_cache/batcher/
# counters/models/fairness/compiles/registry) plus the ONE sanctioned
# addition, `build`. If this test fails, either a surface broke its
# JSON contract or a new key needs to be added HERE deliberately.
GOLDEN_TOP_KEYS = {
    "ready", "reasons", "state", "healthy", "breaker", "cordoned",
    "drain_budget_s", "identity", "scorer_cache", "batcher",
    "counters", "models", "fairness", "compiles", "registry", "build",
}
GOLDEN_SECTIONS = {
    "counters": {"deadline_504", "scored_while_unready",
                 "rate_limited"},
    "batcher": {"requests", "batches", "batched_rows",
                "max_batch_requests", "shed", "fairness_shed",
                "queue_depth"},
    "scorer_cache": {"hits", "misses", "promotions", "evictions",
                     "models", "resident", "resident_bytes",
                     "budget_bytes"},
    "breaker": {"state", "consecutive_failures",
                "cooldown_remaining_s", "trips", "short_circuited",
                "probes", "closes", "failures"},
    "compiles": {"compiles", "compile_s", "pcache_hits",
                 "pcache_misses"},
    "build": {"version", "jax", "jaxlib", "hostfp", "pid",
              "started_at", "uptime_s"},
}


def test_stats_golden_json_shape(stats_server):
    code, st, _ = _get(stats_server, "/3/Stats")
    assert code == 200
    assert set(st.keys()) == GOLDEN_TOP_KEYS, (
        f"/3/Stats top-level shape drifted: "
        f"{sorted(set(st) ^ GOLDEN_TOP_KEYS)}")
    for section, keys in GOLDEN_SECTIONS.items():
        got = set(st[section].keys())
        assert got >= keys, (
            f"/3/Stats[{section}] lost keys: {sorted(keys - got)}")
        if section in ("counters", "batcher", "build"):
            # these sections are EXACT: a stray key is a shape change
            # clients (autoscaler scrapes) would start depending on
            assert got == keys, (
                f"/3/Stats[{section}] gained keys: "
                f"{sorted(got - keys)}")


def test_metrics_inventory_covers_stats(stats_server):
    """THE acceptance diff: every numeric counter on /3/Stats appears
    in the /metrics exposition under the shared naming rule — the two
    surfaces render one registry and cannot drift."""
    # traffic first so per-model series exist
    rows = [{f"x{i}": 0.2 for i in range(4)}]
    code, _, _ = _post(stats_server,
                       "/3/Predictions/models/telem_pm",
                       {"rows": rows})
    assert code == 200
    code, st, _ = _get(stats_server, "/3/Stats")
    assert code == 200
    with urllib.request.urlopen(stats_server + "/metrics",
                                timeout=30) as r:
        assert "text/plain" in r.headers["Content-Type"]
        exposed = parse_prometheus_text(r.read().decode())
    names = {k[0] for k in exposed}

    def leaves(prefix, obj, out):
        for k, v in obj.items():
            if isinstance(v, bool) or isinstance(v, (int, float)):
                out.append(prefix + (str(k),))
            elif isinstance(v, dict):
                leaves(prefix + (str(k),), v, out)

    missing = []
    # plain sections -> h2o_stats_<section>_<leaf...>
    for section, group in (("counters", "counters"),
                           ("batcher", "batcher"),
                           ("scorer_cache", "scorer_cache"),
                           ("compiles", "compiles"),
                           ("breaker", "lifecycle")):
        flat: list = []
        src = st[section]
        pre = (group, "breaker") if section == "breaker" else (group,)
        leaves(pre, src, flat)
        for path in flat:
            if metric_name(*path) not in names:
                missing.append("/".join(path))
    # per-model section -> h2o_stats_models_<counter>{model=...}
    for mkey, rec in st["models"].items():
        for k, v in rec.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                want = (metric_name("models", k),
                        (("model", mkey),))
                if want not in exposed:
                    missing.append(f"models/{mkey}/{k}")
    assert not missing, (
        f"counters on /3/Stats absent from /metrics: {missing}")
    # and the request-phase histograms the registry owns directly
    assert "h2o_request_phase_seconds_bucket" in names


def test_request_trace_spans_and_echo(stats_server):
    rows = [{f"x{i}": 0.1 for i in range(4)}] * 5
    tid = "trace-test-0001"
    code, _, hdrs = _post(stats_server,
                          "/3/Predictions/models/telem_pm",
                          {"rows": rows},
                          headers={"X-H2O-Trace-Id": tid})
    assert code == 200
    low = {k.lower(): v for k, v in hdrs.items()}
    assert low.get("x-h2o-trace-id") == tid
    code, tr, _ = _get(stats_server, f"/3/Trace/{tid}")
    assert code == 200
    assert tr["trace_id"] == tid and tr["model"] == "telem_pm"
    names = [s["name"] for s in tr["spans"]]
    for want in ("admission", "queue", "assemble", "dispatch",
                 "total"):
        assert want in names, f"span '{want}' missing: {names}"
    assert names.count("dispatch") == 1
    total = next(s for s in tr["spans"] if s["name"] == "total")
    disp = next(s for s in tr["spans"] if s["name"] == "dispatch")
    assert 0 <= disp["ms"] <= total["ms"]
    # a request WITHOUT the header gets a minted id echoed back
    code, _, hdrs = _post(stats_server,
                          "/3/Predictions/models/telem_pm",
                          {"rows": rows})
    low = {k.lower(): v for k, v in hdrs.items()}
    minted = low.get("x-h2o-trace-id")
    assert code == 200 and minted and minted != tid
    # unknown id: clean 404
    code, _, _ = _get(stats_server, "/3/Trace/doesnotexist")
    assert code == 404


# ---------------------------------------------------------------------------
# Training spans: one span function from Frame.from_arrays down to
# finalize_train, filed in the same ring as a request (ISSUE 26)
# ---------------------------------------------------------------------------

# the contract PERF.md lists: every child of a `train` root, its kind
TRAIN_SPANS = {
    "train.prepare": "host", "train.bin": "enqueue",
    "train.init_margin": "enqueue", "train.boost": "enqueue",
    "train.dispatch": "enqueue", "train.read_model": "wait",
    "train.metric": "wait", "train.finalize": "host",
}


def _check_tree(spans):
    """ids count from 0, one root, t0 <= t1, children inside parents."""
    by_id = {s["id"]: s for s in spans}
    assert sorted(by_id) == list(range(len(spans)))
    assert [s["id"] for s in spans if s["parent"] is None] == [0]
    for s in spans:
        assert s["t0_ns"] <= s["t1_ns"]
        assert s["ms"] == pytest.approx(
            (s["t1_ns"] - s["t0_ns"]) / 1e6, abs=1e-3)
        if s["parent"] is not None:
            up = by_id[s["parent"]]
            assert up["t0_ns"] <= s["t0_ns"] and s["t1_ns"] <= up["t1_ns"]


def test_train_leaves_one_span_tree(stats_server):
    telemetry.TRACER.clear()
    _train_tiny(seed=7)
    (rec,) = telemetry.TRACER.by_root("train")
    spans = rec["spans"]
    _check_tree(spans)
    root = spans[0]
    assert (root["name"], root["kind"]) == ("train", "host")
    assert root["estimator"] == "GBM" and root["ntrees"] == 2
    assert root["rows"] == 300 and root["features"] == 4
    assert root["chips"] == 8 and root["max_depth"] == 2
    assert {s["name"]: s["kind"] for s in spans[1:]} == TRAIN_SPANS
    by_id = {s["id"]: s for s in spans}
    for s in spans[1:]:
        want = "train.boost" if s["name"] == "train.dispatch" else "train"
        assert by_id[s["parent"]]["name"] == want
    sent = [s for s in spans if s["name"] == "train.dispatch"]
    assert sum(s["trees"] for s in sent) == 2 and sent[0]["first_tree"] == 0
    # the same record a request's would be, at the same endpoint
    code, tr, _ = _get(stats_server, f"/3/Trace/{rec['trace_id']}")
    assert code == 200 and tr["root"] == "train"
    assert [s["name"] for s in tr["spans"]] == [s["name"] for s in spans]
    # the job's frame is a record of its own, listed oldest first
    (fr,) = telemetry.TRACER.by_root("frame.from_arrays")
    _check_tree(fr["spans"])
    assert fr["spans"][0]["columns"] == 5 and fr["spans"][0]["rows"] == 300
    kinds = [(s["name"], s["kind"]) for s in fr["spans"][1:]]
    assert kinds == [("frame.encode", "host"), ("frame.put", "enqueue")
                     ] * 5 + [("frame.settle", "wait")]
    # 300 rows pad to 304 over the 8 shards, 4 bytes a cell
    assert all(s["bytes"] == 304 * 4 for s in fr["spans"][1:-1])
    assert fr["spans"][0]["t1_ns"] <= root["t0_ns"]


@pytest.mark.parametrize("job,source", [
    ("boosted", "margin"), ("forest", "carried"), ("out_of_core", "margin")])
def test_train_metric_says_how_it_was_read(mesh8, monkeypatch, job, source):
    """`train.metric`'s `source`: a boosted job reads its train metric
    off the margin (streamed out of core too), a forest off the sum of
    leaf values its scan carried; the third value, `walk`, is left for
    a forest that holds no such sum (no plan makes one today)."""
    from h2o_kubernetes_tpu.models import DRF

    rng = np.random.default_rng(3)
    cols = {f"x{i}": rng.normal(size=600).astype(np.float32)
            for i in range(4)}
    cols["y"] = np.where(cols["x0"] - cols["x1"] > 0, "late", "ontime")
    fr = h2o.Frame.from_arrays(cols)
    if job == "out_of_core":
        monkeypatch.setenv("H2O_TPU_OOC", "1")
        monkeypatch.setenv("H2O_TPU_OOC_CHUNK_ROWS", "256")
    est = DRF(ntrees=2, max_depth=3, nbins=16, seed=1) if job == "forest" \
        else GBM(ntrees=2, max_depth=2, seed=1)
    est.train(y="y", training_frame=fr)
    spans = telemetry.TRACER.by_root("train")[-1]["spans"]
    boost = next(s for s in spans if s["name"] == "train.boost")
    assert boost["mode"] == ("ooc" if job == "out_of_core" else "in_hbm")
    (metric,) = [s for s in spans if s["name"] == "train.metric"]
    assert metric["source"] == source


def test_trace_off_leaves_no_spans_and_the_same_model(mesh8, monkeypatch):
    on = _train_tiny(seed=11)
    telemetry.TRACER.clear()
    monkeypatch.setenv("H2O_TPU_TRACE", "0")
    hist = telemetry.train_phase_histogram()
    before = hist.snapshot("train.bin")["count"]
    off = _train_tiny(seed=11)
    assert telemetry.TRACER.by_root("train") == []
    assert telemetry.TRACER.by_root("frame.from_arrays") == []
    # the histogram is a counter, not a span record: it stays on
    assert hist.snapshot("train.bin")["count"] == before + 1
    for a, b in zip(on.trees, off.trees):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert on.scoring_history == off.scoring_history


def test_span_kinds_nest_and_list_by_root():
    ring_before = len(telemetry.TRACER.by_root("unit_root"))
    with pytest.raises(ValueError, match="kind"):
        with telemetry.phase_span("unit_root", kind="device"):
            pass
    for i in range(2):
        with telemetry.phase_span("unit_root", n=i) as root:
            with telemetry.phase_span("level_hist", kind="enqueue",
                                      depth=0):
                pass
            root["late"] = True         # attributes the block adds
    recs = telemetry.TRACER.by_root("unit_root")[ring_before:]
    assert [r["spans"][0]["n"] for r in recs] == [0, 1]   # oldest first
    assert recs[0]["trace_id"] != recs[1]["trace_id"]
    for r in recs:
        _check_tree(r["spans"])
        assert r["spans"][0]["late"] is True
        assert (r["spans"][1]["name"], r["spans"][1]["parent"],
                r["spans"][1]["depth"]) == ("level_hist", 0, 0)


def _bare_telemetry():
    """A child's first lines: the telemetry module as `t`, loaded
    beside empty parent packages, so that nothing else of the package
    (and no jax) is imported."""
    pkg = os.path.dirname(os.path.abspath(h2o.__file__))
    return (
        "import sys, types, importlib\n"
        f"pkg = {pkg!r}\n"
        "for name, path in (('h2o_kubernetes_tpu', pkg),\n"
        "                   ('h2o_kubernetes_tpu.runtime',\n"
        "                    pkg + '/runtime')):\n"
        "    m = types.ModuleType(name); m.__path__ = [path]\n"
        "    sys.modules[name] = m\n"
        "t = importlib.import_module(\n"
        "    'h2o_kubernetes_tpu.runtime.telemetry')\n")


def _one_root(name):
    return telemetry.TRACER.by_root(name)[-1]["spans"]


def _busy(seconds):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pass


@pytest.mark.parametrize("what", ["busy", "asleep"])
def test_span_reads_its_threads_cpu(what):
    """`cpu_ms` is the span's own thread on the CPU, so `ms - cpu_ms`
    is the time it was not running: a busy loop reads within 20% of
    its wall time, a sleep near none. Beside other workers the machine
    may take the busy thread off the CPU, and the span then has to say
    that too: it is held to the thread's own CPU clock around the same
    block every time, and to its wall time whenever that clock shows
    the thread was left to run (five tries)."""
    for _ in range(5):
        cpu0, wall0 = time.thread_time(), time.perf_counter()
        with telemetry.phase_span("unit_cpu"):
            _busy(0.1) if what == "busy" else time.sleep(0.05)
        cpu, wall = time.thread_time() - cpu0, time.perf_counter() - wall0
        (root,) = _one_root("unit_cpu")
        assert {"cpu_ms", "sys_ms", "proc_cpu_ms"} <= set(root)
        assert 0 <= root["cpu_ms"] <= root["ms"] + 0.01
        if what == "asleep":
            assert root["cpu_ms"] < 5 and root["ms"] - root["cpu_ms"] > 40
            return
        assert root["cpu_ms"] / 1e3 == pytest.approx(cpu, rel=0.2)
        if cpu >= 0.9 * wall:
            assert root["cpu_ms"] >= 0.8 * root["ms"]
            return


def test_a_childs_readings_lie_inside_its_parents():
    import gc

    with telemetry.phase_span("unit_family"):
        _busy(0.02)
        with telemetry.phase_span("first"):
            _busy(0.03)
            gc.collect()
        with telemetry.phase_span("second"):
            time.sleep(0.01)
    root, first, second = _one_root("unit_family")
    # only a root says what the whole process used
    assert "proc_cpu_ms" in root and "proc_cpu_ms" not in first
    assert root["cpu_ms"] >= first["cpu_ms"] + second["cpu_ms"]
    assert first["cpu_ms"] >= 20 > second["cpu_ms"]
    # the collection ran under `first`: on it and on the root (the
    # fields are inclusive, as `ms` is), not on its sibling
    assert 0 < first["gc_ms"] <= first["ms"]
    assert root["gc_ms"] >= first["gc_ms"] and "gc_ms" not in second
    # counts are left out where they are zero, times never
    assert all(v > 0 for s in (root, first, second)
               for k, v in s.items() if k in ("faults", "switched"))
    # and none of it goes to the TimeLine's copy of the span
    from h2o_kubernetes_tpu.diagnostics import timeline

    ev = [e for e in timeline.events("phase")
          if e.get("phase") == "unit_family"][-1]
    assert not set(ev) & set(telemetry.HOST_FIELDS)


def test_compile_stages_are_credited_to_the_span_that_paid(mesh8):
    """A jitted function's first call under a span leaves the seconds
    of jax's three stages and its name on the innermost open span (and
    up the tree) and in the watch's `by_program`; its second call
    leaves nothing: any `trace_ms` on a later record is a re-trace."""
    import jax
    import jax.numpy as jnp

    from h2o_kubernetes_tpu.runtime.backend import (compile_watch_snapshot,
                                                    start_compile_watch)

    start_compile_watch()

    @jax.jit
    def unit_fresh_program(x):
        return jnp.tanh(x) * 3.0 + jnp.cumsum(x)

    x = jnp.ones(7)
    x.block_until_ready()
    before = compile_watch_snapshot()
    with telemetry.phase_span("unit_compile"):
        with telemetry.phase_span("first_call", kind="enqueue"):
            unit_fresh_program(x)
        with telemetry.phase_span("second_call", kind="enqueue"):
            unit_fresh_program(x)
    root, first, second = _one_root("unit_compile")
    for field in ("trace_ms", "lower_ms", "compile_ms"):
        assert 0 < first[field] <= root[field] <= root["ms"]
    assert first["trace_ms"] + first["lower_ms"] + first["compile_ms"] \
        <= first["ms"]
    assert "unit_fresh_program" in first["programs"]
    assert "unit_fresh_program" in root["programs"]
    assert not set(second) & {"trace_ms", "lower_ms", "compile_ms",
                              "cache_load_ms", "programs"}
    after = compile_watch_snapshot()
    mine = after["by_program"]["unit_fresh_program"]
    assert mine["traces"] == 1 and mine["compiles"] == 1
    assert mine["trace_s"] > 0 and mine["lower_s"] > 0
    assert mine["compile_s"] >= mine["cache_load_s"] >= 0
    assert after["traces"] - before["traces"] >= 1
    for key, field in (("trace_s", "trace_ms"), ("lower_s", "lower_ms"),
                       ("compile_s", "compile_ms")):
        assert (after[key] - before[key]) * 1e3 == pytest.approx(
            root[field], abs=0.01)
    # the stat group lists the programs (a list: the exposition's
    # flattener passes over it, so a program's name mints no series)
    group = telemetry.group_snapshot(["compiles"])["compiles"]
    assert "unit_fresh_program" in [r["program"]
                                    for r in group["by_program"]]
    assert "unit_fresh_program" not in telemetry.prometheus_text()


def test_import_and_init_are_spans_from_the_process_own_zero():
    """A fresh process: the package's import is one `import` root,
    `h2o.init()` one `init` root with its three children and the
    compile watch installed, and the process's start precedes both on
    the same clock."""
    import subprocess
    import sys

    code = (
        "import json, time\n"
        "import h2o_kubernetes_tpu as h2o\n"
        "from h2o_kubernetes_tpu.runtime import backend, telemetry\n"
        "assert not backend._watch_installed\n"
        "h2o.init()\n"
        "print(json.dumps({\n"
        "    'import': telemetry.TRACER.by_root('import'),\n"
        "    'init': telemetry.TRACER.by_root('init'),\n"
        "    'start': telemetry.process_start_ns(),\n"
        "    'now': time.perf_counter_ns(),\n"
        "    'watch': backend._watch_installed}))\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr
    got = json.loads(r.stdout.strip().splitlines()[-1])
    (imp,), (init,) = got["import"], got["init"]
    (root,) = imp["spans"]
    assert root["name"] == "import" and root["parent"] is None
    assert root["modules"] > 100 and 0 < root["cpu_ms"] <= root["ms"] + 1
    assert got["start"] < root["t0_ns"] < root["t1_ns"] <= got["now"]
    # to the tick, and a Python process is up within seconds
    assert root["t0_ns"] - got["start"] < 60e9
    names = [s["name"] for s in init["spans"]]
    assert names == ["init", "init.cache", "init.distributed", "init.mesh"]
    _check_tree(init["spans"])
    assert init["spans"][0]["t0_ns"] >= root["t1_ns"]
    assert init["spans"][3]["devices"] >= 1
    assert all("cpu_ms" in s for s in init["spans"])
    assert got["watch"] is True


def test_frame_encode_says_what_it_encoded(mesh8):
    cols = {"s": np.array(["a", "b", "a", "c"]),
            "f": np.arange(4, dtype=np.float32),
            "d": np.arange(4, dtype=np.float64),
            "b": np.array([True, False, True, True]),
            "g": np.array(["x", "y", "x", "x"], dtype=object),
            "o": np.array(["x", None, "x", "y"], dtype=object)}
    h2o.Frame.from_arrays(cols, domains={"g": ["x", "y"]})
    spans = _one_root("frame.from_arrays")
    enc = {s["column"]: s for s in spans if s["name"] == "frame.encode"}
    assert {c: (s["path"], s["dtype"]) for c, s in enc.items()} == {
        "s": ("factorize_table", "<U1"), "f": ("as_is", "float32"),
        "d": ("cast", "float64"), "b": ("cast", "bool"),
        "g": ("factorize_domain", "object"), "o": ("factorize", "object")}
    assert all(s["bytes"] == 8 * 4 and "cpu_ms" in s for s in enc.values())
    puts = [s for s in spans if s["name"] == "frame.put"]
    assert len(puts) == 6 and all(s["shards"] == 8 for s in puts)


def test_a_wide_frames_tree_is_filed_whole(mesh8):
    """A span tree goes to the ring whole: 138 columns are 278 spans,
    over a request record's bound (which dropped the last columns'
    spans, `y` among them), under a tree's."""
    cols = {f"f{i}": np.zeros(16, np.float32) for i in range(137)}
    cols["y"] = np.array(["a", "b"] * 8)
    h2o.Frame.from_arrays(cols)
    rec = telemetry.TRACER.by_root("frame.from_arrays")[-1]
    assert len(rec["spans"]) == 278 > telemetry.TraceRing.MAX_SPANS
    assert "truncated" not in rec
    assert rec["spans"][-3]["column"] == "y"
    assert rec["spans"][-1]["name"] == "frame.settle"
    # a record that requests merge into keeps its bound
    telemetry.TRACER.record("unit_reused_id", [{"name": "x"}] * 300)
    assert len(telemetry.TRACER.get("unit_reused_id")["spans"]) == 256


def test_trace_off_makes_no_host_reading(mesh8, monkeypatch):
    """With `H2O_TPU_TRACE=0` a span asks the kernel nothing: getrusage
    raises here, and the job trains, bitwise as with the ring on."""
    on = _train_tiny(seed=13)
    assert "cpu_ms" in _one_root("train")[0]

    def refuse(who):
        raise AssertionError("getrusage called with the ring off")

    monkeypatch.setenv("H2O_TPU_TRACE", "0")
    monkeypatch.setattr(telemetry.resource, "getrusage", refuse)
    telemetry.TRACER.clear()
    off = _train_tiny(seed=13)
    assert telemetry.TRACER.by_root("train") == []
    for a, b in zip(on.trees, off.trees):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert on.scoring_history == off.scoring_history


def test_host_readings_need_no_jax():
    """As `test_telemetry_module_needs_no_jax`, for what a span reads
    of the host and for the spans filed after the fact."""
    import subprocess
    import sys

    code = _bare_telemetry() + (
        "import gc, time\n"
        "with t.phase_span('r'):\n"
        "    gc.collect()\n"
        "    t.credit_open_span(programs=('p',), trace_ms=1.5)\n"
        "t.record_root_span('late', 5, 2_000_005, modules=3)\n"
        "(rec,) = t.TRACER.by_root('r')\n"
        "(r,) = rec['spans']\n"
        "assert r['cpu_ms'] >= 0 and r['gc_ms'] > 0, r\n"
        "assert r['trace_ms'] == 1.5 and r['programs'] == ['p'], r\n"
        "(late,) = t.TRACER.by_root('late')[0]['spans']\n"
        "assert late['ms'] == 2.0 and late['modules'] == 3\n"
        "assert t.process_start_ns() < time.perf_counter_ns()\n"
        "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_telemetry_module_needs_no_jax():
    """The router serves this module without a device runtime: loaded
    beside empty parent packages it imports no jax, and a span works
    without the profiler's annotation."""
    import subprocess
    import sys

    code = _bare_telemetry() + (
        "with t.phase_span('r'):\n"
        "    with t.phase_span('c', kind='wait'):\n"
        "        pass\n"
        "(rec,) = t.TRACER.by_root('r')\n"
        "assert [s['name'] for s in rec['spans']] == ['r', 'c']\n"
        "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_train_programs_name_jitted_functions():
    """A trace shows a jitted function as module `jit_<__name__>`:
    every entry of the table is the name of one that exists, so a
    rename breaks this test and not a metric."""
    from h2o_kubernetes_tpu import metrics
    from h2o_kubernetes_tpu.models import gbm
    from h2o_kubernetes_tpu.models.tree import binning, core, ooc

    jitted = {v.__name__ for mod in (gbm, core, binning, ooc, metrics)
              for v in vars(mod).values()
              if callable(v) and hasattr(v, "lower")
              and hasattr(v, "__wrapped__")}
    listed = [n for names in telemetry.TRAIN_PROGRAMS.values()
              for n in names]
    assert len(listed) == len(set(listed))
    assert set(listed) <= jitted, set(listed) - jitted
    assert "<lambda>" not in listed
    # the boost phase is the table `train()` and compile-ahead pick
    # their program from (gbm.BoostPlan)
    assert set(telemetry.TRAIN_PROGRAMS["boost"]) == {
        fn.__name__ for fn in gbm._BOOST_PROGRAMS.values()}
    # the programs a one-chip GBM job runs, by the recorded trace
    for phase, name in (("bin", "_fused_fit_bin_jit"),
                        ("bin", "_bin_block_jit"),
                        ("init", "_init_margin"),
                        ("boost", "_boost_jit"),
                        ("metric", "_logloss_w"),
                        ("metric", "_score_hist_shard")):
        assert name in telemetry.TRAIN_PROGRAMS[phase]


def test_trace_ring_bounded(monkeypatch):
    monkeypatch.setenv("H2O_TPU_TRACE_RING", "16")
    ring = telemetry.TraceRing()
    for i in range(200):
        ring.record(f"t{i}", [{"name": "total", "ms": 1.0}])
    assert ring.get("t0") is None           # aged out
    assert ring.get("t199") is not None     # newest kept
    with ring._lock:
        assert len(ring._ring) <= 16


# ---------------------------------------------------------------------------
# Router hedging: lost/cancelled races never double-count
# ---------------------------------------------------------------------------


class _Stub:
    """Scriptable replica (the test_router idiom, trimmed)."""

    def __init__(self, name, on_post):
        stub = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                body = json.dumps({"ready": True,
                                   "name": stub.name}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                if n:
                    self.rfile.read(n)
                stub.posts.append(dict(self.headers))
                code, payload, hdrs = stub.on_post()
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (hdrs or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

        self.name = name
        self.posts: list = []
        self.on_post = on_post
        self.srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.url = f"http://127.0.0.1:{self.srv.server_address[1]}"
        threading.Thread(target=self.srv.serve_forever,
                         daemon=True).start()

    def close(self):
        self.srv.shutdown()
        self.srv.server_close()


def _fwd_count(model):
    """The tenant's slice of the global forwarded counter — summed
    with `other` because earlier tests in the same process may have
    filled the capped top-K label set (the per-instance by_model
    assertion is the exact one; this diff just proves the registry
    moved by 1 total)."""
    c = REGISTRY.counter(
        "h2o_router_forwarded_total",
        "requests relayed with a non-5xx answer, per tenant "
        "(top-K + other)", label="model")
    return c.value(model) + c.value("other")


def test_hedge_lost_settles_and_never_double_counts(monkeypatch):
    """The satellite fix: a hedge that LOSES the race (hedge leg
    answered, primary's answer relayed) must settle as hedge_lost on
    the hedge shard and add exactly ONE to the tenant's forwarded
    counter — and a hedge still in flight when the primary wins
    settles as hedge_cancelled."""
    monkeypatch.setenv("H2O_TPU_ROUTER_HEALTH_INTERVAL", "30")
    monkeypatch.setenv("H2O_TPU_ROUTER_HEDGE_MS", "30")

    def slow_ok():
        time.sleep(0.15)
        return 200, {"predict": ["ok"], "served_by": "primary"}, None

    def fast_503():
        return 503, {"msg": "draining"}, None

    hold = threading.Event()

    def hung_ok():
        hold.wait(2.0)
        return 200, {"predict": ["ok"], "served_by": "hedge"}, None

    a = _Stub("primary", slow_ok)
    b = _Stub("hedge503", fast_503)
    c = _Stub("hedgehang", hung_ok)
    key_lost, key_cxl = "tlost", "tcxl"
    table = {"keys": {key_lost: ["s0", "s1"], key_cxl: ["s0", "s2"]},
             "shards": {"s0": [a.url], "s1": [b.url], "s2": [c.url]}}
    srv, router = start_router(table)
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        base_lost = _fwd_count(key_lost)
        # LOST race: hedge (fast 503) answers first and fails, slow
        # primary's 200 is relayed
        code, out, hdrs = _post(url, f"/3/Predictions/models/"
                                f"{key_lost}", {"rows": [[1.0]]},
                                headers={"X-H2O-SLO": "interactive"})
        assert code == 200 and out["served_by"] == "primary"
        st = router.snapshot()
        assert st["stats"]["hedges"] == 1
        assert st["stats"]["hedge_wins"] == 0
        assert st["by_shard"]["s1"]["hedge_lost"] == 1
        assert st["by_shard"]["s1"]["hedge_won"] == 0
        assert st["by_shard"]["s1"]["hedge_cancelled"] == 0
        # exactly ONE relayed request for the tenant — the lost hedge
        # did not double-count
        assert st["stats"]["forwarded"] == 1
        assert st["by_model"][key_lost] == 1
        assert _fwd_count(key_lost) - base_lost == 1
        # the trace id survives hedging: both legs carried the SAME id
        tid = {k.lower(): v for k, v in hdrs.items()}[
            "x-h2o-trace-id"]
        leg_tids = {h.get("X-H2O-Trace-Id")
                    for h in a.posts + b.posts}
        assert leg_tids == {tid}
        # CANCELLED race: hedge still hanging when the primary's 200
        # lands
        code, out, _ = _post(url, f"/3/Predictions/models/{key_cxl}",
                             {"rows": [[1.0]]},
                             headers={"X-H2O-SLO": "interactive"})
        assert code == 200 and out["served_by"] == "primary"
        st = router.snapshot()
        assert st["stats"]["hedges"] == 2
        assert st["by_shard"]["s2"]["hedge_cancelled"] == 1
        assert st["by_model"][key_cxl] == 1
        # every fired hedge settled to exactly one outcome
        settled = sum(r["hedge_won"] + r["hedge_lost"]
                      + r["hedge_cancelled"]
                      for r in st["by_shard"].values())
        assert settled == st["stats"]["hedges"]
    finally:
        hold.set()
        router.stop()
        srv.shutdown()
        srv.server_close()
        a.close()
        b.close()
        c.close()


def test_router_metrics_exposition(monkeypatch):
    monkeypatch.setenv("H2O_TPU_ROUTER_HEALTH_INTERVAL", "30")
    a = _Stub("a", lambda: (200, {"predict": ["ok"]}, None))
    table = {"keys": {"pm": ["s0"]}, "shards": {"s0": [a.url]}}
    srv, router = start_router(table)
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        code, _, _ = _post(url, "/3/Predictions/models/pm",
                           {"rows": [[1.0]]})
        assert code == 200
        with urllib.request.urlopen(url + "/metrics",
                                    timeout=30) as r:
            p = parse_prometheus_text(r.read().decode())
        assert p[(metric_name("router", "stats", "requests"),
                  ())] >= 1
        assert p[(metric_name("router", "stats", "forwarded"),
                  ())] >= 1
        # tenant keys never become metric NAMES (capped labels only)
        assert not any("by_model" in k[0] for k in p)
        assert any(k[0] == "h2o_build_info" for k in p)
        assert any(k[0] == "h2o_router_route_seconds_bucket"
                   for k in p)
    finally:
        router.stop()
        srv.shutdown()
        srv.server_close()
        a.close()
