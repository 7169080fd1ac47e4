import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o_kubernetes_tpu.runtime import (ROWS, doall, make_mesh, n_row_shards,
                                        shard_rows, use_mesh)


def test_mesh_shape(mesh8):
    assert n_row_shards(mesh8) == 8
    assert len(jax.devices()) == 8


def test_doall_sum_matches_numpy(mesh8):
    rng = np.random.default_rng(0)
    x = rng.normal(size=1600).astype(np.float32)
    xs = shard_rows(x)
    out = doall(lambda s: dict(total=jnp.sum(s), sq=jnp.sum(s * s)), xs)
    np.testing.assert_allclose(float(out["total"]), x.sum(), rtol=1e-4)
    np.testing.assert_allclose(float(out["sq"]), (x * x).sum(), rtol=1e-4)


def test_doall_min_max(mesh8):
    x = np.arange(64, dtype=np.float32) - 17
    xs = shard_rows(x)
    out = doall(lambda s: dict(lo=jnp.min(s), hi=jnp.max(s)),
                xs, reduce=dict(lo="min", hi="max"))
    assert float(out["lo"]) == -17.0
    assert float(out["hi"]) == 46.0


def test_doall_multiple_inputs(mesh8):
    x = np.arange(80, dtype=np.float32)
    w = np.full(80, 0.5, dtype=np.float32)
    out = doall(lambda a, b: jnp.sum(a * b), shard_rows(x), shard_rows(w))
    np.testing.assert_allclose(float(out), (x * 0.5).sum())


def test_shard_rows_pads_to_multiple(mesh8):
    x = np.ones(13, dtype=np.float32)
    xs = shard_rows(x)
    assert xs.shape[0] == 16
    assert np.isnan(np.asarray(xs)[13:]).all()


def test_submesh(mesh8):
    with use_mesh(make_mesh(n_rows=4, devices=jax.devices()[:4])) as m:
        assert n_row_shards(m) == 4
        x = np.arange(8, dtype=np.float32)
        out = doall(lambda s: jnp.sum(s), shard_rows(x))
        assert float(out) == 28.0


# -- config tiers ------------------------------------------------------------

def test_config_env_and_programmatic(monkeypatch):
    import importlib

    import h2o_kubernetes_tpu.config as C

    monkeypatch.setenv("H2O_TPU_NBINS", "64")
    monkeypatch.setenv("H2O_TPU_LOG_LEVEL", "INFO")
    C.CONFIG.clear()
    C._load()
    assert C.get_config("nbins") == 64
    assert C.get_config("log_level") == "INFO"
    # programmatic tier wins
    C.set_config("nbins", 32)
    assert C.get_config("nbins") == 32
    with pytest.raises(KeyError):
        C.get_config("no_such_key")
    with pytest.raises(ValueError):
        C.set_config("hist_impl", "cuda")
    with pytest.raises(ValueError):
        C.set_config("nbins", 3)
    # restore defaults for the rest of the suite
    monkeypatch.delenv("H2O_TPU_NBINS")
    monkeypatch.delenv("H2O_TPU_LOG_LEVEL")
    C.CONFIG.clear()
    C._load()


def test_config_nbins_flows_into_gbm(monkeypatch):
    import h2o_kubernetes_tpu.config as C
    from h2o_kubernetes_tpu.models import GBM

    C.set_config("nbins", 32)
    try:
        assert GBM(ntrees=1).params.nbins == 32
        assert GBM(ntrees=1, nbins=16).params.nbins == 16   # explicit wins
    finally:
        C.set_config("nbins", 256)


def test_config_hist_impl_flows_into_resolver():
    import h2o_kubernetes_tpu.config as C
    from h2o_kubernetes_tpu.ops.histogram import resolve_impl

    C.set_config("hist_impl", "segment")
    try:
        assert resolve_impl("auto") == "segment"
        assert resolve_impl("pallas") == "pallas"   # explicit wins
    finally:
        C.set_config("hist_impl", "auto")


def test_bad_env_hist_impl_is_loud():
    import h2o_kubernetes_tpu.config as C
    from h2o_kubernetes_tpu.ops.histogram import resolve_impl

    C.CONFIG["hist_impl"] = "pallsa"       # env tier typo
    try:
        with pytest.raises(ValueError, match="pallsa"):
            resolve_impl("auto")
    finally:
        C.CONFIG["hist_impl"] = "auto"


def test_bad_log_level_rejected_before_assignment():
    import h2o_kubernetes_tpu.config as C

    before = C.get_config("log_level")
    with pytest.raises(ValueError, match="log level"):
        C.set_config("log_level", "verbose")
    assert C.get_config("log_level") == before


def test_env_config_validation(monkeypatch):
    """A typo'd H2O_TPU_NBINS must give a clear error, not a bare
    int() traceback at import (r2 ADVICE)."""
    from h2o_kubernetes_tpu import config as C

    monkeypatch.setenv("H2O_TPU_NBINS", "lots")
    with pytest.raises(ValueError, match="bad H2O_TPU_NBINS"):
        C._load()
    monkeypatch.setenv("H2O_TPU_NBINS", "3")
    with pytest.raises(ValueError, match=r"\[4, 256\]"):
        C._load()
    monkeypatch.setenv("H2O_TPU_NBINS", "64")
    C._load()
    assert C.CONFIG["nbins"] == 64
    monkeypatch.delenv("H2O_TPU_NBINS")
    C.CONFIG["nbins"] = 256          # restore the default for the suite


def test_config_table_and_package_agree():
    """`config.py`'s table is the one list of `H2O_TPU_*` names: every
    name the package's code mentions has a row, and every row is a name
    some module other than the table mentions. A name that ends in `_`
    is a prefix (a docstring's `H2O_TPU_RETRY_*`, or one built at run
    time): it needs rows that start with it."""
    import pathlib
    import re

    import h2o_kubernetes_tpu
    from h2o_kubernetes_tpu import config as C

    rows = set(re.findall(r"^\| (H2O_TPU_[A-Z0-9_]+) \|", C.__doc__,
                          re.M))
    assert len(rows) > 70
    read = set()
    pkg = pathlib.Path(h2o_kubernetes_tpu.__file__).parent
    for f in pkg.rglob("*.py"):
        src = f.read_text()
        if f.name == "config.py":
            src = src.replace(C.__doc__, "")
        read |= set(re.findall(r"H2O_TPU_[A-Z0-9_]+", src))
    prefixes = {n for n in read if n.endswith("_")}
    for pre in prefixes:
        assert any(r.startswith(pre) for r in rows), pre
    missing = read - prefixes - rows
    assert not missing, f"read by the package, no row: {sorted(missing)}"
    dead = rows - read
    assert not dead, f"rows nothing reads: {sorted(dead)}"


def test_doall_cache_key_reuses_jit(mesh8):
    """cache_key makes repeated same-computation doall calls reuse one
    jitted callable — rollups across CV fold frames must not recompile
    (an AutoML run paid ~25 warm recompiles before this)."""
    import logging

    import jax

    from h2o_kubernetes_tpu.frame.frame import Frame

    rng = np.random.default_rng(0)
    fr1 = Frame.from_arrays({"a": rng.normal(size=500).astype(np.float32)})
    fr1.vec("a").rollups()            # warm the cached callable

    msgs = []

    class H(logging.Handler):
        def emit(self, record):
            if "Compiling" in record.getMessage():
                msgs.append(record.getMessage())

    h = H()
    jax.config.update("jax_log_compiles", True)
    logging.getLogger("jax").addHandler(h)
    try:
        # same shape, different Vec object: zero new compiles
        fr2 = Frame.from_arrays(
            {"b": rng.normal(size=500).astype(np.float32)})
        r = fr2.vec("b").rollups()
    finally:
        jax.config.update("jax_log_compiles", False)
        logging.getLogger("jax").removeHandler(h)
    assert msgs == [], msgs
    assert np.isfinite(r["mean"])


def test_host_features_fingerprint(tmp_path):
    """The persistent-XLA-cache dir is keyed by a host CPU feature
    fingerprint: a cache copied from an +amx/+avx512 build host can
    never serve a mismatched AOT binary (SIGILL class)."""
    from h2o_kubernetes_tpu.runtime.backend import (
        host_features_fingerprint)

    fp = host_features_fingerprint()
    assert len(fp) == 10 and all(c in "0123456789abcdef" for c in fp)
    assert fp == host_features_fingerprint()          # deterministic
    # flag-set keyed: different features -> different fingerprint,
    # flag ORDER does not matter (kernel ordering isn't stable)
    a = tmp_path / "a"
    a.write_text("flags\t\t: fpu avx2 avx512f amx-tile\n")
    b = tmp_path / "b"
    b.write_text("flags\t\t: fpu avx2\n")
    c = tmp_path / "c"
    c.write_text("flags\t\t: amx-tile avx512f avx2 fpu\n")
    fa = host_features_fingerprint(str(a))
    fb = host_features_fingerprint(str(b))
    fc = host_features_fingerprint(str(c))
    assert fa != fb
    assert fa == fc
    # arm64 spelling
    d = tmp_path / "d"
    d.write_text("Features\t: fp asimd sve\n")
    assert host_features_fingerprint(str(d)) != fa
    # unreadable cpuinfo still fingerprints (platform fallback)
    assert len(host_features_fingerprint(str(tmp_path / "nope"))) == 10


def test_compile_cache_dir_keyed_by_host_features(monkeypatch):
    from h2o_kubernetes_tpu.runtime import backend

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    backend.enable_persistent_compile_cache()
    got = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    assert f"hostfp-{backend.host_features_fingerprint()}" in got
    # ONE fixed place inside the checkout: the path is part of what
    # makes a cache hit, so a second run must resolve the same one
    assert got == backend.repo_cache_dir()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got.startswith(os.path.join(repo, "tools", "_jax_cache"))


def test_compile_cache_dir_placed_from_outside(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program keeps its cache
    there and names no other directory."""
    from h2o_kubernetes_tpu.runtime import backend

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    backend.enable_persistent_compile_cache()
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert list(tmp_path.iterdir()) == []       # nothing probed/written


def test_require_tpu_refuses_cpu():
    """The on-chip scripts (bench/run.py, kernel_gate, chip_smoke) have
    no CPU fallback: without a TPU they exit, naming what they found."""
    from h2o_kubernetes_tpu.runtime.backend import require_tpu

    with pytest.raises(SystemExit, match="platform 'cpu'"):
        require_tpu("test")


def test_dispatch_runahead_is_bounded(mesh8):
    """Canary for conftest._bound_cpu_runahead: 200 back-to-back psum
    programs from one thread, never awaited in between, complete. On
    jaxlib 0.9.0's XLA:CPU this loop deadlocks past 32 in flight
    without the bound (the tier-1 hang of ISSUE 22) — if a jax upgrade
    drops the hook, this test times out by XLA's rendezvous abort
    instead of 26 others hanging at random."""
    from jax.sharding import PartitionSpec as P

    step = jax.jit(jax.shard_map(
        lambda a: a + jax.lax.psum(jnp.sum(a), ROWS) * 1e-9,
        mesh=mesh8, in_specs=P(ROWS), out_specs=P(ROWS)))
    x = shard_rows(np.ones(80_000, np.float32), mesh=mesh8)
    for _ in range(200):
        x = step(x)
    assert np.isfinite(float(x[0]))
