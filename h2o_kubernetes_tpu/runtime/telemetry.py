"""Fleet telemetry — ONE process-wide metrics registry + request tracing.

Before this module the fleet's observability was a pile of per-surface
JSON dicts (`rest.STATS`/`MODEL_STATS`, `scorer_cache_stats()`, the
breaker, router retry budgets, `AutoML.scheduler_stats`, the
`jax.monitoring` compile watch) that only `GET /3/Stats` on one process
at a time could see, with no way to correlate a slow request across the
router hop, the batcher queue, and the device dispatch.  This module is
the single source of truth those surfaces now register through:

- **Metrics registry** (`REGISTRY`): thread-safe counters, gauges and
  bounded-bucket histograms. Label names are validated against a fixed
  allowlist so a typo'd label cannot mint unbounded series, and the
  ``model`` label is cardinality-capped: per metric, the top-K model
  values by traffic keep their own series and everything else rolls up
  into an ``other`` series (``H2O_TPU_METRICS_TOPK``) — a
  thousand-tenant catalog costs K+1 series, not a thousand.
- **Stat groups** (`register_group`): the existing dict surfaces stay
  the storage their owning modules mutate, but they REGISTER here — the
  registry snapshots them for ``/3/Stats`` (byte-shape-compatible with
  the pre-registry JSON) and flattens every numeric leaf into the
  Prometheus text exposition at ``GET /metrics``, so one scrape sees
  every counter ``/3/Stats`` ever reported.
- **Request tracing** (`TRACER`): the router mints an
  ``X-H2O-Trace-Id``, every hop propagates it, and each process records
  its spans (router: per-attempt dispatch outcomes; replica: admission
  wait / batcher queue wait / batch assembly / device dispatch / total)
  into a bounded ring served at ``GET /3/Trace/{id}`` — "why was this
  p99 slow" decomposes into queue-vs-device-vs-hedge.
- **Training spans** (`phase_span`): one span function for the training
  path — `Frame.from_arrays`, every phase of `train()`, the out-of-core
  levels, compile-ahead fills. A span feeds the per-phase histogram,
  the `diagnostics.TimeLine`, the same `TRACER` ring as a request (a
  job's span tree at ``GET /3/Trace/{id}``) and, while jax is loaded,
  the profiler's trace (`h2o.<name>`, beside the device operations).
  `TRAIN_PROGRAMS` names the jitted programs of each phase as a device
  trace shows them. The out-of-core stream also reports the
  upload/compute overlap-efficiency gauge the SCALING docs previously
  estimated by hand.

Deliberately JAX-free and numpy-free: the router and operator processes
scrape and serve this without paying a device import.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
import gc
import os
import sys
import threading
import time
import uuid

try:                                    # Unix only
    import resource
except ImportError:                     # pragma: no cover
    resource = None

from .retry import _env_float

__all__ = [
    "REGISTRY", "TRACER", "MetricsRegistry", "TraceRing",
    "register_group", "group_snapshot", "prometheus_text",
    "parse_prometheus_text", "build_info", "phase_span",
    "record_root_span", "credit_open_span", "process_start_ns",
    "SPAN_KINDS", "HOST_FIELDS", "TRAIN_PROGRAMS",
    "record_request_phases", "new_trace_id", "trace_id_from",
    "count_event", "ooc_stream_account", "start_status_listener",
    "metric_name", "CONTENT_TYPE", "write_metrics",
]

# the Prometheus text exposition content type (0.0.4 is the text format
# every scraper speaks)
CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

# Fixed label-name allowlist: metrics may carry at most ONE label and
# its NAME must come from here — labels are the cardinality lever, and
# an open-ended label vocabulary is how a registry rots into a series
# explosion nobody can aggregate. (`value` is the flattener's label for
# string leaves, `le` is the histogram bucket bound.)
ALLOWED_LABELS = frozenset({
    "model", "shard", "phase", "kind", "slo", "outcome", "state",
    "event", "route", "pool", "replica", "value", "le",
    "version", "jax", "jaxlib", "hostfp", "form",
})

# label names whose VALUE set is unbounded by construction (tenant
# keys): series under them are capped at top-K-by-traffic + "other"
CAPPED_LABELS = frozenset({"model"})

# bounded default buckets (seconds) for latency histograms: 1ms..10s
DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

_PHASES = ("admission", "queue", "assemble", "dispatch", "total")


def _topk() -> int:
    """H2O_TPU_METRICS_TOPK (default 20): per-metric series cap for
    capped labels — the top-K label values by traffic keep their own
    series, the rest roll into `other`."""
    return max(1, int(_env_float("H2O_TPU_METRICS_TOPK", 20.0)))


def _trace_on() -> bool:
    """H2O_TPU_TRACE (default 1): 0 disables span recording (ring +
    per-request phase histograms) — the perf kill switch; counters and
    /metrics stay on."""
    return os.environ.get("H2O_TPU_TRACE", "1") != "0"


def _sanitize(part: str) -> str:
    """A dict key / group name as a Prometheus metric-name component."""
    out = []
    for ch in str(part):
        out.append(ch if ch.isalnum() or ch == "_" else "_")
    s = "".join(out)
    if s and s[0].isdigit():
        s = "_" + s
    return s


def metric_name(*parts: str) -> str:
    """THE /3/Stats-leaf -> /metrics-sample naming rule, shared with
    the inventory-diff test so the two surfaces cannot drift:
    ``metric_name("batcher", "shed") == "h2o_stats_batcher_shed"``."""
    return "_".join(["h2o_stats"] + [_sanitize(p) for p in parts])


def _escape_label(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace("\n", "\\n") \
        .replace('"', '\\"')


# ---------------------------------------------------------------------------
# Metric primitives
# ---------------------------------------------------------------------------


class _LabeledMetric:
    """Shared machinery: one optional label; when the label is capped
    (`model`), series are bounded at top-K by traffic + an `other`
    rollup. All state mutations run under the registry lock (passed
    in), so a multi-threaded hammer loses no updates."""

    kind = "untyped"

    def __init__(self, name: str, help_: str, label: str | None,
                 lock: threading.Lock):
        if label is not None and label not in ALLOWED_LABELS:
            raise ValueError(
                f"metric {name!r}: label {label!r} is not in the "
                f"fixed allowlist {sorted(ALLOWED_LABELS)} — labels "
                "are the cardinality lever; add to the allowlist "
                "deliberately, never ad hoc")
        self.name = name
        self.help = help_
        self.label = label
        self._lock = lock
        self._series: dict[str | None, object] = {}
        # traffic rank for capped labels (bounded itself: evicts the
        # lowest counts past 8*K so the RANKING map cannot become the
        # cardinality leak it exists to prevent)
        self._traffic: dict[str, int] = {}

    def _new_series(self):                       # pragma: no cover
        raise NotImplementedError

    def _merge_into(self, dst, src) -> None:     # pragma: no cover
        raise NotImplementedError

    def _series_for(self, value: str | None):
        """Resolve the series a label value lands in (caller holds the
        lock). Uncapped labels get a series per value — their
        vocabulary is fixed (phases, SLO classes, outcomes)."""
        if self.label is None:
            value = None
        if value is None or self.label not in CAPPED_LABELS:
            s = self._series.get(value)
            if s is None:
                s = self._series[value] = self._new_series()
            return s
        value = str(value)
        k = _topk()
        t = self._traffic
        t[value] = t.get(value, 0) + 1
        if len(t) > 8 * k:
            for v in sorted(t, key=t.get)[: len(t) - 4 * k]:
                if v not in self._series:
                    del t[v]
        s = self._series.get(value)
        if s is not None:
            return s
        named = [v for v in self._series if v not in (None, "other")]
        if len(named) < k:
            s = self._series[value] = self._new_series()
            return s
        # at capacity: a newcomer with MORE traffic than the coldest
        # resident demotes it into `other` and takes its slot — the
        # exposed set converges on the true top-K by traffic
        coldest = min(named, key=lambda v: t.get(v, 0))
        if t[value] > t.get(coldest, 0):
            other = self._series.get("other")
            if other is None:
                other = self._series["other"] = self._new_series()
            self._merge_into(other, self._series.pop(coldest))
            s = self._series[value] = self._new_series()
            return s
        other = self._series.get("other")
        if other is None:
            other = self._series["other"] = self._new_series()
        return other

    def series_count(self) -> int:
        with self._lock:
            return len(self._series)


class Counter(_LabeledMetric):
    kind = "counter"

    def _new_series(self):
        return [0.0]

    def _merge_into(self, dst, src) -> None:
        dst[0] += src[0]

    def inc(self, n: float = 1.0, label_value: str | None = None
            ) -> None:
        with self._lock:
            self._series_for(label_value)[0] += n

    def value(self, label_value: str | None = None) -> float:
        with self._lock:
            s = self._series.get(
                label_value if self.label is not None else None)
            return s[0] if s is not None else 0.0

    def samples(self):
        with self._lock:
            return [(self.name,
                     {self.label: v} if v is not None else {}, s[0])
                    for v, s in self._series.items()]


class Gauge(_LabeledMetric):
    kind = "gauge"

    def __init__(self, name, help_, label, lock, fn=None):
        super().__init__(name, help_, label, lock)
        # callback gauges: fn() -> scalar, read at scrape time
        self._fn = fn

    def _new_series(self):
        return [0.0]

    def _merge_into(self, dst, src) -> None:
        dst[0] = src[0]

    def set(self, v: float, label_value: str | None = None) -> None:
        with self._lock:
            self._series_for(label_value)[0] = float(v)

    def value(self, label_value: str | None = None) -> float:
        if self._fn is not None:
            try:
                return float(self._fn())
            except Exception:  # noqa: BLE001 — a scrape must survive
                return float("nan")
        with self._lock:
            s = self._series.get(
                label_value if self.label is not None else None)
            return s[0] if s is not None else 0.0

    def samples(self):
        if self._fn is not None:
            return [(self.name, {}, self.value())]
        with self._lock:
            return [(self.name,
                     {self.label: v} if v is not None else {}, s[0])
                    for v, s in self._series.items()]


class Histogram(_LabeledMetric):
    """Bounded-bucket histogram: cumulative bucket counts, sum,
    count — the Prometheus shape, quantile-estimable by any scraper."""

    kind = "histogram"

    def __init__(self, name, help_, label, lock, buckets=None):
        super().__init__(name, help_, label, lock)
        self.buckets = tuple(sorted(buckets or DEFAULT_BUCKETS))

    def _new_series(self):
        # [count per bucket..., +Inf count, sum, total count]
        return [0] * (len(self.buckets) + 1) + [0.0, 0]

    def _merge_into(self, dst, src) -> None:
        for i in range(len(src)):
            dst[i] += src[i]

    def observe(self, v: float, label_value: str | None = None) -> None:
        with self._lock:
            s = self._series_for(label_value)
            i = 0
            while i < len(self.buckets) and v > self.buckets[i]:
                i += 1
            s[i] += 1
            s[-2] += v
            s[-1] += 1

    def snapshot(self, label_value: str | None = None) -> dict:
        with self._lock:
            s = self._series.get(
                label_value if self.label is not None else None)
            if s is None:
                return {"count": 0, "sum": 0.0, "buckets": {}}
            cum, out = 0, {}
            for i, b in enumerate(self.buckets):
                cum += s[i]
                out[b] = cum
            return {"count": s[-1], "sum": s[-2], "buckets": out}

    def quantile(self, q: float, label_value: str | None = None
                 ) -> float | None:
        """Linear-interpolated quantile estimate off the buckets (what
        fleet_top renders as p99) — None on an empty series."""
        snap = self.snapshot(label_value)
        n = snap["count"]
        if not n:
            return None
        target = q * n
        prev_b, prev_c = 0.0, 0
        for b, c in snap["buckets"].items():
            if c >= target:
                span = c - prev_c
                frac = (target - prev_c) / span if span else 1.0
                return prev_b + (b - prev_b) * frac
            prev_b, prev_c = b, c
        return self.buckets[-1]

    def samples(self):
        out = []
        with self._lock:
            for v, s in self._series.items():
                labels = {self.label: v} if v is not None else {}
                cum = 0
                for i, b in enumerate(self.buckets):
                    cum += s[i]
                    out.append((self.name + "_bucket",
                                {**labels, "le": f"{b:g}"}, cum))
                out.append((self.name + "_bucket",
                            {**labels, "le": "+Inf"}, cum + s[-3]))
                out.append((self.name + "_sum", labels, s[-2]))
                out.append((self.name + "_count", labels, s[-1]))
        return out


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


class MetricsRegistry:
    """Process-wide metric store + the stat-group registration point.

    First-class metrics (`counter`/`gauge`/`histogram`) are get-or-
    create by name (idempotent — module reimports re-resolve the same
    object). Stat GROUPS are zero-arg snapshot callables the existing
    dict surfaces register; both ``/3/Stats`` and ``/metrics`` render
    from them, which is what makes the registry the single source of
    truth without double-counting a single increment."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: dict[str, _LabeledMetric] = {}
        # name -> (fn, labeled): insertion-ordered, the /3/Stats
        # assembly order
        self._groups: dict = collections.OrderedDict()

    def _get(self, cls, name, help_, label, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help_, label, self._lock, **kw)
                self._metrics[name] = m
            elif not isinstance(m, cls) or m.label != label:
                raise ValueError(
                    f"metric {name!r} re-registered as {cls.__name__}"
                    f"/label={label!r} but exists as "
                    f"{type(m).__name__}/label={m.label!r}")
            return m

    def counter(self, name: str, help_: str = "",
                label: str | None = None) -> Counter:
        return self._get(Counter, name, help_, label)

    def gauge(self, name: str, help_: str = "",
              label: str | None = None, fn=None) -> Gauge:
        return self._get(Gauge, name, help_, label, fn=fn)

    def histogram(self, name: str, help_: str = "",
                  label: str | None = None,
                  buckets=None) -> Histogram:
        return self._get(Histogram, name, help_, label,
                         buckets=buckets)

    # -- stat groups ---------------------------------------------------------

    def register_group(self, name: str, fn, labeled: str | None = None
                       ) -> None:
        """Register a zero-arg dict-snapshot callable. ``labeled``
        names the label the group's TOP-LEVEL keys map to (e.g. the
        per-model counter dict registers ``labeled="model"`` so its
        exposition is ``h2o_stats_models_requests{model=...}`` with
        the top-K + `other` cap applied at scrape time). Idempotent
        by name — last registration wins (in-process restarts)."""
        if labeled is not None and labeled not in ALLOWED_LABELS:
            raise ValueError(f"group {name!r}: label {labeled!r} not "
                             "in the allowlist")
        with self._lock:
            self._groups[name] = (fn, labeled)

    def group_snapshot(self, names=None) -> dict:
        """{group: fn()} — THE /3/Stats payload source. A group whose
        snapshot raises contributes an error marker instead of killing
        the scrape (a stats read must never 500 the probe surface)."""
        with self._lock:
            items = [(n, f) for n, (f, _l) in self._groups.items()
                     if names is None or n in names]
        out = {}
        for n, fn in items:
            try:
                out[n] = fn()
            except Exception as e:  # noqa: BLE001
                out[n] = {"error": repr(e)[:200]}
        return out

    # -- exposition ----------------------------------------------------------

    @staticmethod
    def _flatten(path: tuple, obj, out: list) -> None:
        if isinstance(obj, bool):
            out.append((metric_name(*path), {}, 1.0 if obj else 0.0))
        elif isinstance(obj, (int, float)):
            out.append((metric_name(*path), {}, float(obj)))
        elif isinstance(obj, str):
            # string leaves (breaker/lifecycle state) become an
            # info-style sample: h2o_stats_..._state{value="open"} 1
            out.append((metric_name(*path),
                        {"value": obj[:120]}, 1.0))
        elif isinstance(obj, dict):
            for k, v in obj.items():
                MetricsRegistry._flatten(path + (str(k),), v, out)
        # lists/None: no numeric identity — skipped by design

    @staticmethod
    def _flatten_labeled(group: str, label: str, obj: dict,
                         out: list) -> None:
        """{label_value: {counter: num}} with the top-K-by-traffic +
        `other` rollup applied at scrape time (rank = the series' own
        numeric mass, so the hot tenants keep their series)."""
        k = _topk()
        vals = [(str(lv), rec) for lv, rec in obj.items()
                if isinstance(rec, dict)]

        def mass(rec: dict) -> float:
            return sum(v for v in rec.values()
                       if isinstance(v, (int, float))
                       and not isinstance(v, bool))

        vals.sort(key=lambda it: -mass(it[1]))
        keep, roll = vals[:k], vals[k:]
        rolled: dict[tuple, float] = {}
        for lv, rec in keep:
            flat: list = []
            MetricsRegistry._flatten((group,), rec, flat)
            for name, lbls, v in flat:
                out.append((name, {label: lv, **lbls}, v))
        for _lv, rec in roll:
            flat = []
            MetricsRegistry._flatten((group,), rec, flat)
            for name, lbls, v in flat:
                if lbls:        # string leaves don't aggregate
                    continue
                rolled[(name,)] = rolled.get((name,), 0.0) + v
        for (name,), v in rolled.items():
            out.append((name, {label: "other"}, v))

    def prometheus_text(self, extra_groups: dict | None = None) -> str:
        """The ``GET /metrics`` payload: every first-class metric plus
        every registered stat group's numeric leaves. ``extra_groups``
        lets a per-instance surface (the router) merge its snapshot
        into ITS server's exposition without registering process-wide
        state."""
        lines: list[str] = []
        with self._lock:
            metrics = list(self._metrics.values())
            groups = list(self._groups.items())
        for m in metrics:
            lines.append(f"# HELP {m.name} {m.help}")
            lines.append(f"# TYPE {m.name} {m.kind}")
            for name, labels, v in m.samples():
                lines.append(_render_sample(name, labels, v))
        flat: list = []
        for gname, (fn, labeled) in groups:
            try:
                snap = fn()
            except Exception:  # noqa: BLE001 — one group must not
                continue       # kill the whole exposition
            if labeled and isinstance(snap, dict):
                self._flatten_labeled(gname, labeled, snap, flat)
            else:
                self._flatten((gname,), snap, flat)
        for gname, snap in (extra_groups or {}).items():
            self._flatten((gname,), snap, flat)
        seen_types = set()
        for name, labels, v in flat:
            base = name
            if base not in seen_types:
                seen_types.add(base)
                lines.append(f"# TYPE {base} gauge")
            lines.append(_render_sample(name, labels, v))
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        """Tests only: drop every first-class metric (groups stay —
        their owners registered them at import)."""
        with self._lock:
            self._metrics.clear()


def _render_sample(name: str, labels: dict, v: float) -> str:
    if labels:
        lab = ",".join(f'{k}="{_escape_label(val)}"'
                       for k, val in sorted(labels.items()))
        return f"{name}{{{lab}}} {v:g}"
    return f"{name} {v:g}"


def parse_prometheus_text(text: str) -> dict:
    """Inverse of the exposition (fleet_top + the inventory-diff test):
    {(name, ((label, value), ...)): float}."""
    out: dict = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        try:
            body, _, val = line.rpartition(" ")
            name, labels = body, ()
            if "{" in body:
                name, _, rest = body.partition("{")
                rest = rest.rstrip("}")
                lbls = []
                for part in _split_labels(rest):
                    k, _, v = part.partition("=")
                    lbls.append((k, v.strip('"')
                                 .replace('\\"', '"')
                                 .replace("\\n", "\n")
                                 .replace("\\\\", "\\")))
                labels = tuple(sorted(lbls))
            out[(name, labels)] = float(val)
        except ValueError:
            continue
    return out


def _split_labels(s: str) -> list[str]:
    parts, depth, cur = [], False, []
    for ch in s:
        if ch == '"':
            depth = not depth
        if ch == "," and not depth:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return parts


REGISTRY = MetricsRegistry()


def register_group(name: str, fn, labeled: str | None = None) -> None:
    REGISTRY.register_group(name, fn, labeled)


def group_snapshot(names=None) -> dict:
    return REGISTRY.group_snapshot(names)


def prometheus_text(extra_groups: dict | None = None) -> str:
    return REGISTRY.prometheus_text(extra_groups)


def write_metrics(handler, extra_groups: dict | None = None) -> None:
    """THE GET /metrics response writer — shared by the replica REST
    handler, the router front door, and the status listener so the
    exposition response (content type, headers) cannot drift between
    surfaces. ``handler`` is any BaseHTTPRequestHandler."""
    body = prometheus_text(extra_groups).encode()
    handler.send_response(200)
    handler.send_header("Content-Type", CONTENT_TYPE)
    handler.send_header("Content-Length", str(len(body)))
    handler.end_headers()
    handler.wfile.write(body)


# ---------------------------------------------------------------------------
# Build info
# ---------------------------------------------------------------------------

_STARTED_AT = time.time()
_BUILD: dict | None = None
_BUILD_LOCK = threading.Lock()


def build_info() -> dict:
    """Which build produced this artifact/scrape: package version,
    jax/jaxlib versions (metadata only — NEVER imports jax: the router
    and operator are device-free processes), pid, uptime, and the host
    CPU-feature fingerprint already keying the XLA cache dir."""
    global _BUILD
    with _BUILD_LOCK:
        if _BUILD is None:
            from importlib import metadata

            def _ver(pkg: str) -> str | None:
                try:
                    return metadata.version(pkg)
                except Exception:  # noqa: BLE001
                    return None

            from .backend import host_features_fingerprint

            # package version WITHOUT importing the package: the
            # top-level __init__ pulls the frame/model stack (and jax
            # with it), which a device-free router/operator process
            # must never pay for a version string
            import sys
            pkg = sys.modules.get("h2o_kubernetes_tpu")
            pkg_version = getattr(pkg, "__version__", None)
            if pkg_version is None:
                pkg_version = _ver("h2o_kubernetes_tpu") \
                    or _ver("h2o-kubernetes-tpu")
            if pkg_version is None:
                try:
                    src = os.path.join(os.path.dirname(
                        os.path.dirname(os.path.abspath(__file__))),
                        "__init__.py")
                    with open(src) as f:
                        for line in f:
                            if line.startswith("__version__"):
                                pkg_version = line.split('"')[1]
                                break
                except Exception:  # noqa: BLE001
                    pkg_version = None
            _BUILD = {
                "version": pkg_version,
                "jax": _ver("jax"),
                "jaxlib": _ver("jaxlib"),
                "hostfp": host_features_fingerprint(),
                "pid": os.getpid(),
                "started_at": round(_STARTED_AT, 3),
            }
        out = dict(_BUILD)
    out["uptime_s"] = round(time.time() - _STARTED_AT, 3)
    return out


def _register_build_gauge() -> None:
    """`h2o_build_info{version=...,jax=...,hostfp=...} 1` — the
    Prometheus idiom for build metadata (join on it, never sum it)."""
    b = build_info()

    class _Info(Gauge):
        def samples(self):
            return [("h2o_build_info",
                     {k: str(b.get(k)) for k in
                      ("version", "jax", "jaxlib", "hostfp")}, 1.0)]

    with REGISTRY._lock:
        REGISTRY._metrics.setdefault(
            "h2o_build_info",
            _Info("h2o_build_info",
                  "build identity (constant 1; labels carry it)",
                  None, REGISTRY._lock))


_register_build_gauge()


# ---------------------------------------------------------------------------
# Request tracing
# ---------------------------------------------------------------------------


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def trace_id_from(headers) -> str:
    """The propagation contract: take X-H2O-Trace-Id when present and
    well-formed (alnum/_/- up to 64 chars — a header is attacker
    input and becomes a dict key + response header), else mint."""
    raw = headers.get("X-H2O-Trace-Id") if headers is not None else None
    if raw:
        tid = str(raw).strip()[:64]
        if tid and all(c.isalnum() or c in "-_" for c in tid):
            return tid
    return new_trace_id()


class TraceRing:
    """Bounded per-process span store: trace_id -> span record. The
    ring (H2O_TPU_TRACE_RING entries, default 512) evicts oldest-
    inserted, so a serving storm can never grow it — recent traces are
    the debuggable ones anyway."""

    def __init__(self, capacity: int | None = None):
        self._lock = threading.Lock()
        self._ring: collections.OrderedDict[str, dict] = \
            collections.OrderedDict()
        self._cap = capacity

    def _capacity(self) -> int:
        if self._cap is not None:
            return self._cap
        return max(8, int(_env_float("H2O_TPU_TRACE_RING", 512.0)))

    # spans kept per RECORD: the ring bounds record count, this bounds
    # a single record — a client reusing one (valid-looking) trace id
    # for every request must not grow one record without limit
    MAX_SPANS = 256
    # ... and a span tree that `phase_span` files whole, under an id it
    # minted itself: nobody can add to it, so its bound only stops a
    # runaway loop of spans. (A 138-column frame's tree is 277 spans:
    # under MAX_SPANS its last columns' spans were dropped, the string
    # response's among them.)
    MAX_TREE_SPANS = 4096

    def record(self, trace_id: str, spans, limit: int | None = None,
               **meta) -> None:
        """Append spans under ``trace_id`` (merging with an existing
        record — a hedged request's two legs land on one trace).
        Past ``limit`` (MAX_SPANS) per record, further spans are
        dropped and the record is flagged ``truncated`` (a reused id
        is a client bug or an attack, never a reason for unbounded
        memory)."""
        if not _trace_on():
            return
        with self._lock:
            rec = self._ring.get(trace_id)
            if rec is None:
                rec = {"trace_id": trace_id, "ts": time.time(),
                       "spans": []}
                self._ring[trace_id] = rec
                while len(self._ring) > self._capacity():
                    self._ring.popitem(last=False)
            room = (limit or self.MAX_SPANS) - len(rec["spans"])
            if room <= 0:
                rec["truncated"] = True
            else:
                spans = list(spans)
                if len(spans) > room:
                    rec["truncated"] = True
                rec["spans"].extend(spans[:room])
            for k, v in meta.items():
                rec.setdefault(k, v)

    def get(self, trace_id: str) -> dict | None:
        with self._lock:
            rec = self._ring.get(trace_id)
            return None if rec is None else {
                **rec, "spans": list(rec["spans"])}

    def by_root(self, name: str) -> list[dict]:
        """The records whose root span is ``name`` (`phase_span` files
        a training job under its root's name), oldest first."""
        with self._lock:
            return [{**rec, "spans": list(rec["spans"])}
                    for rec in self._ring.values()
                    if rec.get("root") == name]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


TRACER = TraceRing()


def request_phase_histogram() -> Histogram:
    return REGISTRY.histogram(
        "h2o_request_phase_seconds",
        "per-request serving phase latency "
        "(admission|queue|assemble|dispatch|total)", label="phase")


def record_request_phases(trace_id: str | None, marks: dict,
                          t_start: float, t_end: float,
                          model: str | None = None,
                          slo: str | None = None,
                          kind: str = "score",
                          outcome: str | None = None) -> list:
    """Turn the batcher's timestamp marks into named spans, feed the
    per-phase latency histograms (+ the per-model total-latency
    histogram, capped top-K), and file the span record under
    ``trace_id``. Returns the span list (the route echoes nothing —
    GET /3/Trace/{id} serves it). ``outcome`` marks a FAILED request
    (shed/504/breaker/timeout error name): the slow requests tracing
    exists to debug are exactly the ones that die in the queue, so
    they must appear in the ring and the histograms too — phases
    without marks (never dispatched) simply contribute no span."""
    hist = request_phase_histogram()

    def span(name, a, b):
        if a is None or b is None or b < a:
            return None
        dur = b - a
        hist.observe(dur, label_value=name)
        return {"name": name, "ms": round(dur * 1000.0, 3)}

    spans = [s for s in (
        span("admission", marks.get("admit"), marks.get("enqueue")),
        span("queue", marks.get("enqueue"), marks.get("pop")),
        span("assemble", marks.get("pop"), marks.get("dispatch_start")),
        span("dispatch", marks.get("dispatch_start"),
             marks.get("dispatch_end")),
        span("total", t_start, t_end),
    ) if s is not None]
    if t_start is not None:
        REGISTRY.histogram(
            "h2o_request_seconds",
            "end-to-end request latency per model (top-K + other)",
            label="model").observe(t_end - t_start,
                                   label_value=model)
    if trace_id:
        meta = {"model": model, "slo": slo, "kind": kind,
                "hop": "replica"}
        if outcome is not None:
            meta["outcome"] = outcome
        TRACER.record(trace_id, spans, **meta)
    return spans


# ---------------------------------------------------------------------------
# Training phase spans
# ---------------------------------------------------------------------------

# What a span's seconds are, on a backend that runs device work
# asynchronously. No span ends in a block_until_ready of its own: that
# would serialise the dispatch pipeline the boost loop and the
# out-of-core double buffer are built on. The device's side of a phase
# is in the device trace, under the names below.
#   host     the block computes on the host
#   enqueue  the block only queues device work: its seconds are the
#            dispatch, not the work (a dispatch can itself block while
#            the runtime's queue is full: the record's `cpu_ms` says so)
#   wait     the block reads a device result back, so it also waits
#            for whatever was queued before it
SPAN_KINDS = ("host", "enqueue", "wait")

# The jitted functions of the training path by phase: their `__name__`s,
# which a profiler trace shows as module names (`jit_<name>(<hash>)`).
# Eager one-operation programs (`jit_concatenate`, `jit__threefry_split`,
# `jit__unstack`, ...) belong to no phase. tests/test_telemetry.py holds
# every entry to a jitted function that exists, so a rename breaks a
# test and not a metric.
TRAIN_PROGRAMS = {
    "bin": ("_fused_fit_bin_jit", "_bin_block_jit", "_concat_blocks",
            "_col_sample", "_device_quantiles", "apply_bins"),
    "init": ("_init_margin", "_stack_predict"),
    "boost": ("_boost_jit", "_boost_multi_jit"),
    # the out-of-core stream's programs, a few per level and chunk
    "boost_ooc": ("_chunk_grads_jit", "_chunk_root_hist_jit",
                  "_chunk_desc_hist_jit", "_root_logic_jit",
                  "_level_logic_jit", "_final_leaves_jit",
                  "_chunk_finish_jit", "_chunk_goss_max_jit",
                  "_chunk_goss_counts_jit", "_goss_threshold_jit",
                  "_chunk_goss_compact_jit", "_chunk_goss_margin_jit"),
    "metric": ("sigmoid", "softmax", "exp", "_logloss_w", "_logloss_unw",
               "_pad_jit", "_auc_impl", "_score_hist_shard",
               "_auc_of_score_hist", "_rmse_w", "_rmse_unw"),
}

# What the host did under a span, on the ring's record beside its
# times (docs/OBSERVABILITY.md "Training spans" has the table). All
# inclusive, as `ms` is: a reader takes a span's own part as the span
# less its children.
#   cpu_ms                the span's own thread on the CPU, user +
#                         system (the thread's CPU clock at the span's
#                         two ends): `ms - cpu_ms` is the time the
#                         thread was not running (blocked in a dispatch
#                         or a read-back, or switched out)
#   sys_ms                the system part, by getrusage(RUSAGE_THREAD)
#                         at the two ends. Both always there where the
#                         platform can say, zero or not
#   faults, switched      page faults (minor + major) and involuntary
#                         context switches of that thread, by the same
#                         two calls; where non-zero
#   proc_cpu_ms           root spans: every thread of the process
#                         (RUSAGE_SELF), the compiler's pool among them
#   gc_ms                 collector pauses inside the span (`_on_gc`)
#   trace_ms, lower_ms,   jax's trace / lower / backend-compile stages
#   compile_ms,           under the span, each second under the innermost
#   cache_load_ms,        stage (cache loads lie inside compile_ms); how
#   traces, programs      many programs were traced, and the names of
#                         those that paid: the compile watch
#                         (runtime/backend.py) credits them
_CREDITED = ("gc_ms", "trace_ms", "lower_ms", "compile_ms",
             "cache_load_ms", "traces")
HOST_FIELDS = ("cpu_ms", "sys_ms", "faults", "switched", "proc_cpu_ms",
               *_CREDITED, "programs")
MAX_PROGRAMS = 8        # names a span keeps; then `programs_more` counts

# the record's own fields: kept out of the TimeLine's copy of a span
_SPAN_FIELDS = frozenset(
    {"name", "id", "parent", "kind", "t0_ns", "t1_ns", "ms",
     *HOST_FIELDS, "programs_more"})

# the open span of this thread of control: (trace id, the root's span
# list, this span's id)
_OPEN_SPAN: contextvars.ContextVar = contextvars.ContextVar(
    "h2o_open_span", default=None)


def train_phase_histogram() -> Histogram:
    return REGISTRY.histogram(
        "h2o_train_phase_seconds",
        "host seconds of a training span, by span name (train.bin, "
        "train.dispatch, frame.encode, level_hist, ...); a span that "
        "only enqueues device work reads the dispatch, one that reads "
        "a result back reads the wait for the device",
        label="phase",
        buckets=(0.001, 0.005, 0.025, 0.1, 0.5, 2.0, 10.0, 60.0,
                 300.0))


# getrusage's selectors; RUSAGE_THREAD is Linux's. Where either is
# missing the fields it gives are absent from a record, never zero
_THREAD = getattr(resource, "RUSAGE_THREAD", None)
_PROCESS = getattr(resource, "RUSAGE_SELF", None)
_thread_cpu_ns = getattr(time, "thread_time_ns", None)

_gc_hook_lock = threading.Lock()
_gc_hooked = False
_gc_started = threading.local()


def _cpu_ms(before, after) -> float:
    return round((after.ru_utime - before.ru_utime
                  + after.ru_stime - before.ru_stime) * 1e3, 3)


def _thread_now() -> tuple:
    """(the calling thread's CPU clock, its getrusage): one end of a
    span; None for what the platform lacks."""
    return (_thread_cpu_ns() if _thread_cpu_ns is not None else None,
            resource.getrusage(_THREAD) if _THREAD is not None else None)


def _thread_usage(rec: dict, before: tuple, after: tuple) -> None:
    """What the thread used between two `_thread_now()`s, as a
    record's fields. (`cpu_ms` is the thread's clock and not
    getrusage's sum: the kernel adds a running thread's time up at its
    tick, so over a span of a few milliseconds getrusage can say more
    CPU than wall time. The split into user and system is sampled at
    the tick too: `sys_ms` is good over many spans or a long one.)"""
    (cpu0, ru0), (cpu1, ru1) = before, after
    if cpu0 is not None:
        rec["cpu_ms"] = round((cpu1 - cpu0) / 1e6, 3)
    if ru0 is None:
        return
    rec["sys_ms"] = round((ru1.ru_stime - ru0.ru_stime) * 1e3, 3)
    for name, n in (
            ("faults", ru1.ru_minflt - ru0.ru_minflt
             + ru1.ru_majflt - ru0.ru_majflt),
            ("switched", ru1.ru_nivcsw - ru0.ru_nivcsw)):
        if n:
            rec[name] = n


def _add_programs(rec: dict, names, more: int = 0) -> None:
    have = rec.setdefault("programs", [])
    for name in names:
        if name in have:
            continue
        if len(have) < MAX_PROGRAMS:
            have.append(name)
        else:
            more += 1
    if more:
        rec["programs_more"] = rec.get("programs_more", 0) + more


def credit_open_span(programs=(), **credit) -> None:
    """Add to the credited fields (``gc_ms=``, ``trace_ms=``,
    ``traces=``, ...) and the program names of the innermost span open
    on the calling thread; nothing where none is open. The span hands
    them on to its parent when it ends, so the fields are inclusive as
    its times are."""
    here = _OPEN_SPAN.get()
    if here is None:
        return
    rec = here[1][here[2]]
    for field, v in credit.items():
        rec[field] = rec.get(field, 0) + v
    if programs:
        _add_programs(rec, programs)


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks`: a collection runs on the thread that set it off,
    between a `start` and a `stop` call."""
    if phase == "start":
        _gc_started.ns = time.perf_counter_ns()
        return
    t0 = getattr(_gc_started, "ns", None)
    if t0 is not None:
        _gc_started.ns = None
        credit_open_span(gc_ms=(time.perf_counter_ns() - t0) / 1e6)


def _hook_gc() -> None:
    global _gc_hooked
    with _gc_hook_lock:
        if not _gc_hooked:
            gc.callbacks.append(_on_gc)
            _gc_hooked = True


@contextlib.contextmanager
def phase_span(phase: str, kind: str = "host", **data):
    """THE span of the training path: times a block on
    ``time.perf_counter_ns()`` into the per-phase histogram, the
    diagnostics TimeLine (kind="phase"), the trace ring and — while
    jax is loaded — the profiler's own trace.

    A span opened inside another becomes its child; one opened with
    none open is a root, mints a trace id, and files the whole tree in
    `TRACER` when it ends, so ``GET /3/Trace/{id}`` serves a training
    job as it serves a request. A span record holds ``name``, ``id``
    and ``parent`` (ids count from 0 within the trace), ``kind`` (see
    SPAN_KINDS), ``t0_ns``/``t1_ns``, ``ms``, what the host did
    meanwhile (HOST_FIELDS) and the attributes; the block may add
    attributes to the dict it is given. The same span lies in any
    profile taken meanwhile (`diagnostics.profile()`) as
    ``h2o.<name>``, on the profiler's clock beside the device
    operations, with the attributes it was opened with.
    ``H2O_TPU_TRACE=0`` switches the ring, the host's readings and the
    annotation off; histogram and TimeLine stay."""
    if kind not in SPAN_KINDS:
        raise ValueError(f"span kind {kind!r} not in {SPAN_KINDS}")
    rec = dict(data)
    token = note = used0 = proc0 = None
    if _trace_on():
        if not _gc_hooked:
            _hook_gc()
        tid, spans, parent = _OPEN_SPAN.get() or (new_trace_id(), [], None)
        ident = len(spans)
        spans.append(rec)               # in the order the spans opened
        token = _OPEN_SPAN.set((tid, spans, ident))
        jax = sys.modules.get("jax")
        if jax is not None:
            # never imports jax: the router serves this module without
            # a device runtime
            note = jax.profiler.TraceAnnotation(
                "h2o." + phase,
                **{k: v for k, v in data.items() if v is not None})
            note.__enter__()
        if parent is None and _PROCESS is not None:
            proc0 = resource.getrusage(_PROCESS)
    t0 = time.perf_counter_ns()
    if token is not None:
        used0 = _thread_now()
    try:
        yield rec
    finally:
        used1 = _thread_now() if used0 is not None else None
        t1 = time.perf_counter_ns()
        dur = (t1 - t0) / 1e9
        if token is not None:
            if note is not None:
                note.__exit__(None, None, None)
            _OPEN_SPAN.reset(token)
            rec.update(name=phase, id=ident, parent=parent, kind=kind,
                       t0_ns=t0, t1_ns=t1, ms=round(dur * 1000.0, 3))
            _thread_usage(rec, used0, used1)
            # what was credited to this span goes on to its parent
            up = None if parent is None else spans[parent]
            for field in _CREDITED:
                v = rec.get(field)
                if v is None:
                    continue
                if isinstance(v, float):
                    v = rec[field] = round(v, 3)
                if up is not None:
                    up[field] = up.get(field, 0) + v
            if up is None:
                if proc0 is not None:
                    rec["proc_cpu_ms"] = _cpu_ms(
                        proc0, resource.getrusage(_PROCESS))
                TRACER.record(tid, spans, limit=TraceRing.MAX_TREE_SPANS,
                              root=phase)
            elif "programs" in rec:
                _add_programs(up, rec["programs"],
                              rec.get("programs_more", 0))
        train_phase_histogram().observe(dur, label_value=phase)
        try:
            from ..diagnostics import timeline

            timeline.record(
                "phase", phase, phase=phase,
                dur_ms=round(dur * 1000.0, 3),
                **{k: v for k, v in rec.items()
                   if k not in _SPAN_FIELDS})
        except Exception:  # noqa: BLE001 — accounting only
            pass


def record_root_span(phase: str, t0_ns: int, t1_ns: int, **data) -> None:
    """File a root span after the fact, from two stamps of
    ``time.perf_counter_ns()``: for a block that starts before this
    module can be imported (the package's own `import`). Ring and
    histogram only: no host readings were taken at its ends, and the
    profiler's annotation cannot be written late."""
    dur = (t1_ns - t0_ns) / 1e9
    if _trace_on():
        rec = dict(data, name=phase, id=0, parent=None, kind="host",
                   t0_ns=t0_ns, t1_ns=t1_ns, ms=round(dur * 1000.0, 3))
        TRACER.record(new_trace_id(), [rec], root=phase)
    train_phase_histogram().observe(dur, label_value=phase)


@functools.cache
def process_start_ns() -> int | None:
    """The process's start on ``time.perf_counter_ns()``'s clock, so
    that a reader can lay the ring's first records out from the
    process's own zero: `/proc/self/stat`'s start time (field 22, in
    ticks since boot) against CLOCK_BOOTTIME, to the tick. None where
    that cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            # the second field is the command in brackets, and may
            # hold spaces: count from its closing bracket
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age_ns = time.clock_gettime_ns(time.CLOCK_BOOTTIME) \
            - ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
        return time.perf_counter_ns() - age_ns
    except (OSError, ValueError, IndexError, AttributeError):
        return None


# -- out-of-core stream overlap accounting ----------------------------------
#
# The ooc chunk stream double-buffers host->device uploads against the
# histogram build (arXiv:2005.09148's design); SCALING.md previously
# ESTIMATED how well that overlap works. The stream now reports it:
# upload seconds (time blocked in device_put), compute seconds (time
# the consumer held the generator suspended), and the derived
# overlap-efficiency gauge compute/(compute+upload) — 1.0 means every
# upload hid fully under compute.

_OOC_LOCK = threading.Lock()
_OOC = {"upload_s": 0.0, "compute_s": 0.0, "wall_s": 0.0, "streams": 0}


def ooc_stream_account(upload_s: float, compute_s: float,
                       wall_s: float) -> None:
    with _OOC_LOCK:
        _OOC["upload_s"] += upload_s
        _OOC["compute_s"] += compute_s
        _OOC["wall_s"] += wall_s
        _OOC["streams"] += 1
    REGISTRY.counter("h2o_ooc_upload_seconds_total",
                     "time blocked uploading ooc chunks").inc(upload_s)
    REGISTRY.counter("h2o_ooc_compute_seconds_total",
                     "consumer compute time over the ooc stream"
                     ).inc(compute_s)
    denom = _OOC["upload_s"] + _OOC["compute_s"]
    REGISTRY.gauge(
        "h2o_ooc_overlap_ratio",
        "fraction of stream time spent computing (1.0 = uploads "
        "fully hidden under compute)").set(
        _OOC["compute_s"] / denom if denom > 0 else 0.0)


def ooc_overlap_snapshot() -> dict:
    with _OOC_LOCK:
        out = dict(_OOC)
    denom = out["upload_s"] + out["compute_s"]
    out["overlap_ratio"] = round(out["compute_s"] / denom, 4) \
        if denom > 0 else None
    return out


register_group("ooc_stream", ooc_overlap_snapshot)


# ---------------------------------------------------------------------------
# Operator events
# ---------------------------------------------------------------------------


def count_event(kind: str) -> None:
    """Reconciler/ShardedPool events re-registered through the
    registry (`h2o_operator_events_total{event=...}`) — the durable
    store keeps the ring, /metrics keeps the rates."""
    REGISTRY.counter("h2o_operator_events_total",
                     "operator reconcile events by kind",
                     label="event").inc(label_value=str(kind)[:64])


# ---------------------------------------------------------------------------
# Status listener (operator.run / any device-free process)
# ---------------------------------------------------------------------------


def start_status_listener(port: int, host: str = "127.0.0.1",
                          extra_groups=None):
    """A tiny /metrics + /healthz HTTP listener for processes that do
    not run the full REST node (the operator). ``extra_groups`` is a
    zero-arg callable -> dict merged into the exposition. Returns the
    server (``server_address[1]`` is the bound port — pass 0 for an
    ephemeral one); None when port is None. The CALLER owns the
    off-by-default policy (operator.run starts one only when
    --status-port / H2O_TPU_METRICS_PORT says so). Never imports jax
    or rest.py."""
    if port is None:
        return None
    import json
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class _StatusHandler(BaseHTTPRequestHandler):
        server_version = "h2o-tpu-status/1"

        def log_message(self, *a):
            pass

        def _send(self, code, body: bytes, ctype: str):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            path = self.path.split("?", 1)[0].rstrip("/")
            if path == "/metrics":
                eg = None
                if extra_groups is not None:
                    try:
                        eg = extra_groups()
                    except Exception:  # noqa: BLE001
                        eg = None
                return write_metrics(self, eg)
            if path == "/healthz":
                return self._send(
                    200, json.dumps(
                        {"alive": True, "build": build_info()}
                    ).encode(), "application/json")
            return self._send(404, b"not found", "text/plain")

    srv = ThreadingHTTPServer((host, int(port)), _StatusHandler)
    t = threading.Thread(target=srv.serve_forever,
                         name="h2o-tpu-status", daemon=True)
    t.start()
    return srv
