r"""Configuration tiers — the reference's flag system, TPU-shaped.

Reference (SURVEY.md §5.6): three tiers — CRD spec (declarative),
CLI flags, and H2O-3 runtime options (`H2O.OptArgs` command line,
`sys.ai.h2o.*` system properties, `H2O_KUBERNETES_*` env vars). Here:

1. CRD spec → the C++ operator (native/deployment/crd.*) — declarative.
2. Env vars (`H2O_TPU_*`) → read once at import, listed below.
3. Programmatic `set_config(key, value)` — the in-process tier, wins
   over env.

| env var | default | meaning |
|---|---|---|
| H2O_TPU_LOG_LEVEL | WARNING | package logger level (water/util/Log) |
| H2O_TPU_HIST_IMPL | auto | histogram kernel: auto/pallas/segment |
| H2O_TPU_NBINS | 256 | default tree-learner bin count |
| H2O_TPU_COORDINATOR | — | jax.distributed coordinator (runtime/mesh) |
| H2O_TPU_NUM_PROCESSES | 1 | multi-host process count (runtime/mesh) |
| H2O_TPU_PROCESS_ID | 0 | this host's process id (runtime/mesh) |
| H2O_TPU_HIST_BYTES_BUDGET | 2³⁰ | deep-tree level-histogram memory budget (models/gbm validation, the out-of-core trigger) |
| H2O_TPU_CV_SHAPE_SHARE_ROWS | tpu≤1M | weights-masked CV row threshold; 0 disables, N forces on any backend (models/cv) |
| H2O_TPU_ARROW_CSV | 1 | 0 disables the pyarrow CSV fast path (frame/parse) |
| H2O_TPU_INGEST_CHUNK_BYTES | 16 MiB | pyarrow record-batch size for streamed CSV ingest (frame/parse, docs/SCALING.md) |
| H2O_TPU_DEVICE_GATHER_MIN | 65536 | row threshold for the on-device Vec.select_rows gather; 0 forces it, below it the host path wins (frame/frame) |
| H2O_TPU_EFB | auto | Exclusive Feature Bundling for wide sparse frames: 0 kill switch, 1 force, auto = plan on >= MIN_F-feature frames, keep when the shrink gate passes (models/tree/efb, docs/SCALING.md) |
| H2O_TPU_EFB_CONFLICT | 0 | allowed conflict-ROW fraction per bundle (LightGBM max_conflict_rate analog); 0 = exact exclusivity, the parity-gated default |
| H2O_TPU_EFB_MIN_F | 64 | feature-count floor below which auto mode skips EFB planning entirely (narrow frames keep the fused no-host-sync prologue) |
| H2O_TPU_EFB_MIN_SHRINK | 0.75 | auto mode keeps a plan only when bundled width Fb <= this fraction of F |
| H2O_TPU_GOSS | 0 (off) | GOSS gradient-based one-side sampling for the boosted-tree growers (GBM + XGBoost-hist; DRF stays bagged): per round keep the top-TOP_A row fraction by \|gradient\| + a seeded RAND_B fraction of the rest amplified by (1-a)/b, compacted into a static buffer so histogram kernels stream ~(a+b)·rows per level; 0 restores unsampled training bit-for-bit (models/gbm.goss_params, docs/SCALING.md "Gradient-based sampling") |
| H2O_TPU_GOSS_TOP_A | 0.1 | GOSS: fraction of rows kept outright by top \|gradient\| rank (0 <= a < 1, a + b <= 1) |
| H2O_TPU_GOSS_RAND_B | 0.1 | GOSS: seeded random fraction of the remaining rows kept with (1-a)/b weight amplification (0 < b, a + b <= 1) |
| H2O_TPU_OOC | auto | out-of-core tree training: 1 force, 0 never, auto = binned matrix past the budget headroom (models/gbm, docs/SCALING.md) |
| H2O_TPU_OOC_CHUNK_ROWS | derived | rows per host-pinned binned chunk in out-of-core mode (models/tree/ooc) |
| H2O_TPU_OOC_RESIDENT | 0 | debug: keep out-of-core chunks device-resident (the bitwise streamed-vs-resident parity harness) |
| H2O_TPU_SCORER_CACHE_BYTES | 1 GiB | byte budget over every resident model's serving state (live traces + LUTs + device flat arrays); past it the least-recently-scored model's executables/device arrays are evicted and re-promote via the persistent XLA cache; <=0 unbounded (models/base, docs/SERVING.md) |
| H2O_TPU_SCORER_CACHE_MAX | 0 (off) | optional resident-model COUNT cap on top of the byte budget; evictions counted in scorer_cache_stats() (models/base) |
| H2O_TPU_SCORE_FAIRNESS | 1 | per-model queue-share caps + SLO-priority dispatch in the micro-batcher; 0 = unfair FIFO baseline (rest.py, docs/SERVING.md) |
| H2O_TPU_SCORE_MODEL_QUEUE_SHARE | per class | global override of the admission-queue fraction ONE model may occupy (rest.py) |
| H2O_TPU_SLO_DEFAULT | standard | SLO class (interactive/standard/batch) when neither the X-H2O-SLO header nor the model's registry default applies (rest.py) |
| H2O_TPU_MODEL_RATE_LIMIT | 0 (off) | per-tenant token bucket: sustained scoring requests/second any ONE model key may submit (burst = 1 s of traffic); past it 429 + Retry-After at admission, counted in /3/Stats `rate_limited` (rest.py, docs/SERVING.md) |
| H2O_TPU_PCACHE_MIN_SECS | — | persistent-XLA-cache compile-time threshold override; serving pods pin 0 so every tenant compile persists and evictions re-promote from disk (runtime/backend.py) |
| H2O_TPU_SCORE_BATCH_US | 2000 | REST scoring micro-batcher window, µs; 0 = dispatch immediately (rest.py, docs/SERVING.md) |
| H2O_TPU_SCORE_TIMEOUT | 60 | seconds a scoring request may wait for its micro-batched result before 503 (rest.py) |
| H2O_TPU_SCORE_MAX_ROWS | 100000 | per-request row cap on the inline scoring route (413 past it — one oversized dispatch must not lock the cloud) |
| H2O_TPU_CONTRIB_MAX_ROWS | 100000 | per-request row cap on the TreeSHAP contributions route (413 past it; rest.py, docs/SERVING.md "Explainable serving") |
| H2O_TPU_CONTRIB_CHUNK | 16384 | upper bound on rows per device TreeSHAP dispatch — the kernel's [rows × leaves × depth] working set is chunked under it, pow2-floored so full chunks share one trace key (models/base.py) |
| H2O_TPU_CONTRIB_SLO_DEFAULT | explain | SLO class for contributions requests when no X-H2O-SLO header is sent (rest.py; the model's scoring registry default deliberately does not apply) |
| H2O_TPU_SHAP_KERNEL | auto | TreeSHAP serving impl: auto = chip-native Pallas kernel on TPU / lowered-XLA `flat_shap_tab` elsewhere, 1 forces the kernel (interpret mode off-chip), 0 kill switch restoring the XLA path bitwise; read at TRACE time like hist_impl — a cached contributions executable keeps its impl until scorer-cache evict/re-promote (ops/shap_kernel.py, docs/SERVING.md "Explainable serving") |
| H2O_TPU_JOB_TIMEOUT | 0 (off) | server-side job-poll timeout: RUNNING jobs older than this read FAILED on /3/Jobs (rest.py) |
| H2O_TPU_SCORE_QUEUE_MAX | 256 | scoring admission-queue bound: requests past it are load-shed with 429 + Retry-After; <=0 unbounded (rest.py, docs/RESILIENCE.md) |
| H2O_TPU_DRAIN_TIMEOUT | 30 | seconds the SIGTERM drain waits for RUNNING jobs / batcher flush before failing them (runtime/lifecycle.py) |
| H2O_TPU_BREAKER_FAILURES | 5 | consecutive device-dispatch errors that trip the serving circuit breaker open (runtime/lifecycle.py) |
| H2O_TPU_BREAKER_COOLDOWN | 30 | seconds the breaker stays open before admitting the half-open probe (runtime/lifecycle.py) |
| H2O_TPU_RETRY_ATTEMPTS | 5 | total attempts of a retried persist/HTTP call (runtime/retry.py; read per call) |
| H2O_TPU_RETRY_BASE | 0.2 | first retry backoff, seconds; doubles per attempt with jitter |
| H2O_TPU_RETRY_MAX_DELAY | 10 | per-sleep cap of a retry loop, seconds |
| H2O_TPU_RETRY_DEADLINE | 120 | total sleep budget of a retry loop, seconds |
| H2O_TPU_RETRY_DISABLE | — (off) | 1 = single attempt, no sleeps (chaos drills prove a fault reaches the retry path) |
| H2O_TPU_RETRY_MAX_ELAPSED_S | 0 (off) | hard cap on a retry loop's total elapsed time, attempts included (runtime/retry.py) |
| H2O_TPU_AUTOML_PIPELINE | 1 | 0 kills the pipelined AutoML executor AND the CV fold pipeline — restores the serial path bit-for-bit (runtime/scheduler.py, docs/SCALING.md) |
| H2O_TPU_AUTOML_COMPILE_AHEAD | 1 | plan entries whose boost executables are pre-lowered ahead of the training cursor; 0 disables the compile stream (needs the persistent XLA cache to pay — auto-disabled without it) |
| H2O_TPU_AUTOML_QUEUE_DEPTH | 4 | bound on the scheduler's host/compile queues: completed-but-unapplied models and stale compile requests cannot accumulate (runtime/scheduler.py) |
| H2O_TPU_POOL_REPLICA | — | 1 marks this rest.py process an operator-provisioned scorer replica: /readyz additionally requires a pushed+warmed registry artifact (rest.py, docs/OPERATOR.md) |
| H2O_TPU_POOL_WARM_BUCKETS | 128,1024 | default warm-up ladder: Model.warm_up pre-traces every pow2 batch bucket up to the largest listed, before a replica's readyz flips (models/base.py) |
| H2O_TPU_POOL_RECONCILE_INTERVAL | 0.5 | seconds between scorer-pool reconcile passes (operator/reconcile.py) |
| H2O_TPU_POOL_STARTUP_DEADLINE | 180 | seconds a provisioned replica may take to reach READY before the reconciler replaces it |
| H2O_TPU_POOL_DEREGISTER_GRACE | 0.75 | cordon→SIGTERM gap of a rolling update, so routers drop the endpoint before the drain begins (zero-5xx contract) |
| H2O_TPU_POOL_QUEUE_HIGH | 8 | mean admission-queue depth per replica that scales the pool up (operator/autoscale.py) |
| H2O_TPU_POOL_PROBE_TIMEOUT | 2 | per-probe cap on every reconciler health/readyz//3/Stats scrape — one hung replica cannot stall the whole reconcile pass (operator/reconcile.py) |
| H2O_TPU_POOL_BACKOFF_BASE | 0.5 | crash-loop backoff: first respawn delay after a replica failure; doubles per recent failure (operator/reconcile.py, docs/OPERATOR.md) |
| H2O_TPU_POOL_BACKOFF_MAX | 30 | crash-loop backoff delay cap, seconds |
| H2O_TPU_POOL_BACKOFF_WINDOW | 120 | seconds a failure stays in the backoff history; a version clean this long respawns immediately again |
| H2O_TPU_POOL_ROLLOUT_RETRIES | 3 | new-version readiness failures before a surge-one rollout auto-rolls-back to the pinned last-good version (`rollout_rolled_back` event) |
| H2O_TPU_POOL_LOG_MAX_BYTES | 8 MiB | per-replica log size that triggers rotate-on-respawn (operator/reconcile.py) |
| H2O_TPU_POOL_LOG_KEEP | 16 | replica log files kept per pool; older ones are pruned at spawn so a crash loop cannot fill the disk the durable store lives on |
| H2O_TPU_ROUTER_RETRY_BUDGET | 2 | fleet router: per-TENANT cross-shard retry budget, retries/second (burst = 1 s, min 1 token); 0 = no retries, every failure relays to the client — a dying shard must not amplify load onto survivors (operator/router.py, docs/OPERATOR.md "Sharded routing") |
| H2O_TPU_ROUTER_HEDGE_MS | 0 (off) | hedged-dispatch kill switch: > 0 arms speculative re-dispatch for `interactive`-class requests after this many ms without a primary answer (first response wins; hedges consume retry-budget tokens) |
| H2O_TPU_ROUTER_HEALTH_INTERVAL | 0.5 | seconds between router health sweeps over every replica's /3/Stats; each scrape rides the shared probe helper (H2O_TPU_POOL_PROBE_TIMEOUT + 3 attempts before unhealthy, so a scoring burst can't flap a shard out of the ring) |
| H2O_TPU_ROUTER_MAX_INFLIGHT | 256 | router admission bound on concurrently forwarded requests; past it 429 + Retry-After (<=0 unbounded) |
| H2O_TPU_ROUTER_TIMEOUT | 30 | per-forward upstream timeout on the router, seconds; clamped under the request's remaining X-H2O-Deadline-Ms budget |
| H2O_TPU_ROUTER_TABLE_INTERVAL | 0 | extra throttle, seconds, between STORE reads of the published routing table by a stateless router (`StoreRoutingTable`); 0 = refresh on every health sweep (operator/router.py, docs/OPERATOR.md "Router HA & rebalancing") |
| H2O_TPU_LEASE_TTL | 5 | controller-lease TTL, seconds: an `operator.run --ha` replica that misses renewals this long is structurally deposed (epoch bump fences its routing writes) and a standby takes over (operator/spec.py, docs/OPERATOR.md) |
| H2O_TPU_LEASE_HEARTBEAT | ttl/3 | seconds between the lease holder's renew heartbeats (operator/run.py) |
| H2O_TPU_REBALANCE | 0 (off) | live hot-shard rebalancing: 1 lets the controller MOVE a sustained-pressure tenant to the next healthy shard in its HRW preference, make-before-break (operator/reconcile.py, docs/OPERATOR.md "Router HA & rebalancing") |
| H2O_TPU_REBALANCE_SUSTAIN | 3 | consecutive reconcile passes a tenant's shed/504 delta must stay positive before it counts as hot — one blip never moves anyone |
| H2O_TPU_REBALANCE_COOLDOWN | 30 | seconds between moves, fleet-wide: rebalancing converges one tenant at a time instead of thrashing |
| H2O_TPU_REBALANCE_RETIRE_S | 5 | make-before-break dwell: seconds the move's SOURCE keeps serving after the destination took routing-preference position 0, and only while the destination stays healthy |
| H2O_TPU_REBALANCE_FAILBACK_S | 30 | failback hygiene for loss-driven re-placements: once every home shard of an overridden tenant has been healthy this long, the override copies age out of the survivor's child spec and the routing table |
| H2O_TPU_FAULTS | — (off) | fault-injection spec `site:kind[@n][~p];...` armed for the process (runtime/faults.py; chaos drills and tests only) |
| H2O_TPU_SVMLIGHT_DENSE_BUDGET | 200000000 | cells (rows × max feature index) an SVMLight import may densify to before it is refused (frame/parse.py) |
| H2O_TPU_WEBHDFS | — | namenode HTTP address (http://namenode:9870) that `hdfs://` URIs are read and written through (persist_cloud.py) |
| H2O_TPU_METRICS_TOPK | 20 | fleet telemetry: per-metric series cap for tenant-cardinality labels (`model`) — the top-K label values by traffic keep their own series, everything else rolls into `other`, so 1000 tenants cost K+1 series on GET /metrics (runtime/telemetry.py, docs/OBSERVABILITY.md) |
| H2O_TPU_METRICS_PORT | — (off) | operator.run status listener: bind /metrics + /healthz on this port so the control plane is scrapeable like any replica (0 = ephemeral; `--status-port` overrides) |
| H2O_TPU_TRACE | 1 | 0 disables request-span recording (trace ring + per-request phase histograms) — the tracing perf kill switch; counters and /metrics stay on (runtime/telemetry.py) |
| H2O_TPU_TRACE_RING | 512 | per-process bound on retained trace records (GET /3/Trace/{id}); oldest-inserted evict, so a serving storm cannot grow the ring |
| JAX_COMPILATION_CACHE_DIR | auto | persistent XLA cache dir; h2o.init() picks repo/user default when unset (keyed by host CPU feature fingerprint) |

COORDINATOR/NUM_PROCESSES/PROCESS_ID are the operator's injection
contract, consumed directly by `runtime/mesh.initialize_distributed`.
The knobs below the line are read at USE time by their owning modules
(perf/robustness switches, not cluster identity), so they stay
env-only rather than entering the programmatic tier.

Caveat: `hist_impl` is read when a training program is TRACED; XLA
executables already compiled for a shape keep the kernel they were
traced with, so changing it mid-process affects new shapes only (the
usual jit-static-argument semantics).
"""

from __future__ import annotations

import logging
import os
from typing import Any

__all__ = ["get_config", "set_config", "CONFIG"]

_DEFAULTS: dict[str, Any] = {
    "log_level": "WARNING",
    "hist_impl": "auto",
    "nbins": 256,
}

_ENV_KEYS = {
    "log_level": "H2O_TPU_LOG_LEVEL",
    "hist_impl": "H2O_TPU_HIST_IMPL",
    "nbins": "H2O_TPU_NBINS",
}

CONFIG: dict[str, Any] = {}


def _validate(key: str, value: Any) -> Any:
    """ONE rule set for both tiers (env `_load` and programmatic
    `set_config`); returns the coerced value or raises ValueError."""
    if key == "nbins":
        value = int(value)
        if not 4 <= value <= 256:
            raise ValueError("nbins must be in [4, 256]")
    if key == "hist_impl" and value not in ("auto", "pallas", "segment"):
        raise ValueError(f"hist_impl must be auto/pallas/segment, "
                         f"got '{value}'")
    if key == "log_level" and not isinstance(
            getattr(logging, str(value).upper(), None), int):
        raise ValueError(f"unknown log level '{value}'")
    return value


def _load() -> None:
    """Env tier. Shares _validate with set_config — a typo'd
    H2O_TPU_NBINS must produce a clear message, not crash the package
    import inside int()."""
    for key, default in _DEFAULTS.items():
        raw = os.environ.get(_ENV_KEYS[key])
        if raw is None:
            CONFIG.setdefault(key, default)
            continue
        try:
            CONFIG[key] = _validate(key, raw)
        except (ValueError, TypeError) as e:
            raise ValueError(
                f"bad {_ENV_KEYS[key]}={raw!r}: {e}") from None


def get_config(key: str) -> Any:
    if key not in _DEFAULTS:
        raise KeyError(f"unknown config key '{key}' "
                       f"(known: {sorted(_DEFAULTS)})")
    return CONFIG[key]


def set_config(key: str, value: Any) -> None:
    """Programmatic tier — applies immediately (and re-levels the
    package logger for log_level)."""
    if key not in _DEFAULTS:
        raise KeyError(f"unknown config key '{key}' "
                       f"(known: {sorted(_DEFAULTS)})")
    value = _validate(key, value)   # raises BEFORE assignment
    CONFIG[key] = value
    if key == "log_level":
        from .diagnostics import log

        log.setLevel(getattr(logging, str(value).upper()))


_load()
