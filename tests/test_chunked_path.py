"""Chunk-parity suite for the 10M-row training data path (ISSUE 3):

- streamed pyarrow record-batch CSV ingest == monolithic parse,
  column-exact (and a truncated stream fails loudly — never a short
  frame);
- ``Frame.binned`` (column-block binning straight from Frame columns)
  == ``apply_bins_jit(frame.to_matrix(...), ...)`` bitwise, plus the
  host-chunked variant the out-of-core trainer consumes;
- out-of-core chunk-streamed GBM == the resident-chunk mode bitwise
  (the staging machinery must not touch a single bit), == the
  monolithic fused path bitwise where the histogram sums are exact
  (single gaussian round on a ±0.5-gradient response), and close in
  float elsewhere;
- the jitted-scorer cache LRU cap (H2O_TPU_SCORER_CACHE_MAX);
- the device-gather Vec.select_rows fold-slice path.
"""

import numpy as np
import pytest

import h2o_kubernetes_tpu as h2o
from h2o_kubernetes_tpu.frame import Frame
from h2o_kubernetes_tpu.frame.parse import import_file
from h2o_kubernetes_tpu.models import GBM
from h2o_kubernetes_tpu.models.tree import binning
from h2o_kubernetes_tpu.models.tree.binning import (apply_bins_jit,
                                                    bin_frame_host_chunks,
                                                    fit_bins)
from tools import datasets as D


def _mixed_frame(n=1800, seed=3):
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=n).astype(np.float32)
    x2 = (rng.exponential(2.0, size=n)).astype(np.float32)
    x2[rng.random(n) < 0.05] = np.nan
    c = np.array(["u", "v", "w"])[rng.integers(0, 3, size=n)]
    hc = rng.integers(0, 400, size=n).astype(np.float32)  # > n_bins levels
    y = np.where(x1 + 0.4 * x2 * 0 + (c == "u") +
                 rng.normal(scale=0.6, size=n) > 0.5, "yes", "no")
    return h2o.Frame.from_arrays(
        {"x1": x1, "x2": x2, "c": c, "hc": hc, "y": y},
        domains={"hc": [f"L{i}" for i in range(400)]})


# ---------------------------------------------------------------------------
# Streamed parse
# ---------------------------------------------------------------------------

def _frames_equal(fr, fr2):
    assert fr.names == fr2.names
    assert fr.nrows == fr2.nrows
    for n in fr.names:
        a, b = fr.vec(n), fr2.vec(n)
        assert a.domain == b.domain, n
        x = np.asarray(a.data)[: fr.nrows]
        y = np.asarray(b.data)[: fr2.nrows]
        np.testing.assert_array_equal(x, y, err_msg=n)


def test_streamed_chunks_match_single_batch(tmp_path, monkeypatch,
                                            mesh8):
    """Forcing many tiny record batches must be BITWISE identical to
    one big batch — chunk boundaries cannot leak into values, codes,
    or domains."""
    p = str(tmp_path / "air.csv")
    D.airlines_csv(p, 3_000, chunk=3_000)
    monkeypatch.delenv("H2O_TPU_ARROW_CSV", raising=False)
    monkeypatch.delenv("H2O_TPU_INGEST_CHUNK_BYTES", raising=False)
    fr = import_file(p)
    assert fr.nrows == 3_000
    monkeypatch.setenv("H2O_TPU_INGEST_CHUNK_BYTES", str(16 << 10))
    fr2 = import_file(p)
    _frames_equal(fr, fr2)


def test_streamed_parse_matches_python_parse(tmp_path, monkeypatch,
                                             mesh8):
    """The streamed arrow reader reproduces the pure-Python parser
    (which DEFINES the parse semantics) on the airlines shape:
    identical names, domains, codes; numerics to float tolerance (the
    two paths parse decimal floats through different routines)."""
    p = str(tmp_path / "air.csv")
    D.airlines_csv(p, 2_000, chunk=2_000)
    monkeypatch.delenv("H2O_TPU_ARROW_CSV", raising=False)
    monkeypatch.setenv("H2O_TPU_INGEST_CHUNK_BYTES", str(64 << 10))
    fr = import_file(p)
    monkeypatch.setenv("H2O_TPU_ARROW_CSV", "0")
    fr2 = import_file(p)
    assert fr.names == fr2.names
    for n in fr.names:
        a, b = fr.vec(n), fr2.vec(n)
        assert a.domain == b.domain, n
        x = np.asarray(a.data)[: fr.nrows]
        y = np.asarray(b.data)[: fr2.nrows]
        if a.is_enum():
            np.testing.assert_array_equal(x, y, err_msg=n)
        else:
            assert np.allclose(x, y, equal_nan=True), n


def test_truncated_csv_fails_loudly(tmp_path, monkeypatch, mesh8):
    """A stream aborting mid-record must fail the parse — both paths —
    never ship a short frame (the chaos drill rehearses the same at
    20k rows). The cut lands two fields into a record (same rule as
    chaos.py _mid_record_cut): a cut at a record boundary or inside
    the last field parses legally as a shorter file and can't test
    this."""
    p = str(tmp_path / "t.csv")
    D.airlines_csv(p, 500, chunk=500)
    with open(p, "rb") as f:
        blob = f.read()
    line_start = blob.rindex(b"\n", 0, int(len(blob) * 0.6)) + 1
    with open(p, "r+b") as f:
        f.truncate(blob.index(b",", line_start) + 1)
    monkeypatch.delenv("H2O_TPU_ARROW_CSV", raising=False)
    with pytest.raises(Exception):
        import_file(p)
    monkeypatch.setenv("H2O_TPU_ARROW_CSV", "0")
    with pytest.raises(ValueError, match="columns"):
        import_file(p)


def test_short_row_fails_loudly(tmp_path, mesh8):
    p = tmp_path / "s.csv"
    p.write_text("a,b,c\n1,2,3\n4,5\n")
    with pytest.raises(ValueError, match="columns"):
        import_file(str(p))


# ---------------------------------------------------------------------------
# Frame.binned
# ---------------------------------------------------------------------------

def test_frame_binned_matches_apply_bins_bitwise(mesh8, monkeypatch):
    fr = _mixed_frame()
    names = ["x1", "x2", "c", "hc"]
    spec = fit_bins(fr, names, n_bins=64)
    # two columns a block, so the block seam is exercised
    monkeypatch.setattr(binning, "_BIN_BLOCK_BYTES",
                        2 * 4 * fr.vec("x1").padded_len)
    got = np.asarray(fr.binned(spec))
    import jax.numpy as jnp

    want = np.asarray(apply_bins_jit(
        fr.to_matrix(names), jnp.asarray(spec.edges_matrix()),
        jnp.asarray(np.array(spec.is_enum)), spec.na_bin))
    np.testing.assert_array_equal(got, want)


def test_frame_binned_lru_refreshes_on_hit(mesh8):
    """A,B,A,C with cap 2 must keep A (a hit refreshes recency) —
    FIFO would evict the just-used A and re-pay a full binning pass."""
    fr = _mixed_frame(n=400, seed=7)
    sa = fit_bins(fr, ["x1", "x2", "c"], n_bins=16)
    sb = fit_bins(fr, ["x1", "x2"], n_bins=16)
    sc = fit_bins(fr, ["x1"], n_bins=16)
    a = fr.binned(sa)
    fr.binned(sb)
    assert fr.binned(sa) is a             # hit → A most recent
    fr.binned(sc)                         # evicts B, not A
    assert fr.binned(sa) is a


def test_frame_binned_cache_and_invalidation(mesh8):
    fr = _mixed_frame(n=600, seed=5)
    names = ["x1", "x2", "c"]
    spec = fit_bins(fr, names, n_bins=32)
    a = fr.binned(spec)
    assert fr.binned(spec) is a           # cache hit
    fr["extra"] = fr["x1"] + 1.0          # mutation invalidates
    assert fr.binned(spec) is not a


def test_host_chunks_match_frame_binned(mesh8):
    fr = _mixed_frame(n=700, seed=6)
    names = ["x1", "x2", "c", "hc"]
    spec = fit_bins(fr, names, n_bins=32)
    full = np.asarray(fr.binned(spec))
    chunk_rows = 256
    bufs = bin_frame_host_chunks(fr, spec, chunk_rows)
    padded = fr.vec("x1").padded_len
    cat = np.concatenate(bufs)[:padded]
    np.testing.assert_array_equal(cat, full)
    # rows past the padded length carry the NA bin
    assert (np.concatenate(bufs)[padded:] == spec.na_bin).all()


# ---------------------------------------------------------------------------
# Out-of-core GBM parity
# ---------------------------------------------------------------------------

def _exact_gaussian_frame(n=4096, seed=11):
    """y ∈ {0,1} with an exactly even split: the gaussian prior is
    exactly 0.5, first-round gradients are ±0.5, and every histogram
    partial sum is exactly representable — chunk-boundary f32
    reassociation cannot change a bit."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 5)).astype(np.float32)
    y = np.zeros(n, dtype=np.float32)
    y[rng.permutation(n)[: n // 2]] = 1.0
    cols = {f"f{i}": X[:, i] for i in range(5)}
    cols["y"] = y
    return h2o.Frame.from_arrays(cols)


def _tree_arrays(m):
    import jax

    return [np.asarray(a) for a in jax.tree.flatten(m.trees)[0]]


def test_ooc_matches_resident_bitwise(mesh8, monkeypatch):
    """Streamed (host-pinned, double-buffered device_put) chunks vs
    device-resident chunks: same chunk grid, same adds — every tree
    array and every prediction must be bit-identical."""
    rng = np.random.default_rng(0)
    n = 2048
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.where(X[:, 0] + 0.5 * X[:, 1] +
                 rng.normal(scale=0.5, size=n) > 0, "p", "n")
    cols = {f"f{i}": X[:, i] for i in range(4)}
    cols["y"] = y
    monkeypatch.setenv("H2O_TPU_OOC", "1")
    monkeypatch.setenv("H2O_TPU_OOC_CHUNK_ROWS", "512")
    monkeypatch.delenv("H2O_TPU_OOC_RESIDENT", raising=False)
    fr = h2o.Frame.from_arrays(dict(cols))
    m_stream = GBM(ntrees=3, max_depth=3, seed=7).train(
        y="y", training_frame=fr)
    monkeypatch.setenv("H2O_TPU_OOC_RESIDENT", "1")
    fr2 = h2o.Frame.from_arrays(dict(cols))
    m_res = GBM(ntrees=3, max_depth=3, seed=7).train(
        y="y", training_frame=fr2)
    for a, b in zip(_tree_arrays(m_stream), _tree_arrays(m_res)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(m_stream.predict_raw(fr),
                                  m_res.predict_raw(fr))


def test_ooc_matches_monolithic_bitwise_exact_sums(mesh8, monkeypatch):
    """Chunk-accumulated vs fused-monolithic on the exact-sum gaussian
    construction: bitwise-equal trees, margins and predictions."""
    fr = _exact_gaussian_frame()
    kw = dict(ntrees=1, max_depth=3, distribution="gaussian", seed=3,
              min_rows=4.0)
    monkeypatch.setenv("H2O_TPU_OOC", "0")
    m_mono = GBM(**kw).train(y="y", training_frame=fr)
    monkeypatch.setenv("H2O_TPU_OOC", "1")
    monkeypatch.setenv("H2O_TPU_OOC_CHUNK_ROWS", "1024")
    m_ooc = GBM(**kw).train(y="y", training_frame=fr)
    assert float(m_mono.init_score) == float(m_ooc.init_score) == 0.5
    for a, b in zip(_tree_arrays(m_mono), _tree_arrays(m_ooc)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(m_mono.predict_raw(fr),
                                  m_ooc.predict_raw(fr))
    h_m = m_mono.scoring_history[-1]["train_rmse"]
    h_o = m_ooc.scoring_history[-1]["train_rmse"]
    assert h_m == h_o


def test_ooc_close_to_monolithic_multitree(mesh8, monkeypatch):
    """Multi-tree bernoulli: later rounds' gradients are general f32,
    so chunk-boundary reassociation may flip low-order bits — the
    models must still agree to float tolerance."""
    rng = np.random.default_rng(1)
    n = 3072
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = np.where(X[:, 0] - 0.7 * X[:, 2] +
                 rng.normal(scale=0.4, size=n) > 0, "y", "n")
    cols = {f"f{i}": X[:, i] for i in range(6)}
    cols["y"] = y
    fr = h2o.Frame.from_arrays(cols)
    monkeypatch.setenv("H2O_TPU_OOC", "0")
    m_mono = GBM(ntrees=5, max_depth=4, seed=2).train(
        y="y", training_frame=fr)
    monkeypatch.setenv("H2O_TPU_OOC", "1")
    monkeypatch.setenv("H2O_TPU_OOC_CHUNK_ROWS", "1024")
    m_ooc = GBM(ntrees=5, max_depth=4, seed=2).train(
        y="y", training_frame=fr)
    p1 = m_mono.predict_raw(fr)
    p2 = m_ooc.predict_raw(fr)
    assert np.allclose(p1, p2, atol=2e-3), np.abs(p1 - p2).max()
    a1 = m_mono.scoring_history[-1]["train_auc"]
    a2 = m_ooc.scoring_history[-1]["train_auc"]
    assert abs(a1 - a2) < 5e-3


def test_ooc_gate_keeps_cadence_and_sampling_in_hbm(mesh8, monkeypatch):
    """score_every and sample_rate<1 are OOC-ineligible even when
    H2O_TPU_OOC=1 forces the mode: a requested scoring cadence must
    never be dropped, and a row-sample draw must never depend on the
    chunk-size knob — both train on the in-HBM path instead."""
    fr = _exact_gaussian_frame(n=1024, seed=12)
    monkeypatch.setenv("H2O_TPU_OOC", "1")
    monkeypatch.setenv("H2O_TPU_OOC_CHUNK_ROWS", "256")
    kw = dict(max_depth=2, distribution="gaussian", seed=1)
    m = GBM(ntrees=4, score_every=2, **kw).train(
        y="y", training_frame=fr)
    assert len(m.scoring_history) >= 2    # cadence honored
    m1 = GBM(ntrees=3, sample_rate=0.8, **kw).train(
        y="y", training_frame=fr)
    monkeypatch.setenv("H2O_TPU_OOC_CHUNK_ROWS", "128")
    m2 = GBM(ntrees=3, sample_rate=0.8, **kw).train(
        y="y", training_frame=fr)
    for a, b in zip(_tree_arrays(m1), _tree_arrays(m2)):
        np.testing.assert_array_equal(a, b)   # chunk knob can't matter
    # col subsampling: fused vs streamed key schedules differ, so it
    # must gate to the in-HBM path — OOC on/off can't change the model
    m3 = GBM(ntrees=3, col_sample_rate_per_tree=0.6, **kw).train(
        y="y", training_frame=fr)
    monkeypatch.setenv("H2O_TPU_OOC", "0")
    m4 = GBM(ntrees=3, col_sample_rate_per_tree=0.6, **kw).train(
        y="y", training_frame=fr)
    for a, b in zip(_tree_arrays(m3), _tree_arrays(m4)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_ooc_1m_row_exact_parity(mesh8, monkeypatch):
    """The tier-1 exact-sum construction at 1M rows with forced small
    chunks — the scale point where the streamed path actually streams
    (≈29 chunks of 36k rows)."""
    fr = _exact_gaussian_frame(n=1_000_000, seed=4)
    kw = dict(ntrees=1, max_depth=4, distribution="gaussian", seed=5)
    monkeypatch.setenv("H2O_TPU_OOC", "0")
    m_mono = GBM(**kw).train(y="y", training_frame=fr)
    monkeypatch.setenv("H2O_TPU_OOC", "1")
    monkeypatch.setenv("H2O_TPU_OOC_CHUNK_ROWS", "36864")
    m_ooc = GBM(**kw).train(y="y", training_frame=fr)
    for a, b in zip(_tree_arrays(m_mono), _tree_arrays(m_ooc)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Satellites
# ---------------------------------------------------------------------------

def test_scorer_cache_lru_eviction(mesh8, monkeypatch):
    from h2o_kubernetes_tpu.models import base as MB

    rng = np.random.default_rng(2)
    n = 256
    X = rng.normal(size=(n, 3)).astype(np.float32)
    y = np.where(X[:, 0] > 0, "a", "b")
    cols = {f"f{i}": X[:, i] for i in range(3)}
    cols["y"] = y
    fr = h2o.Frame.from_arrays(cols)
    monkeypatch.delenv("H2O_TPU_OOC", raising=False)
    models = [GBM(ntrees=2, max_depth=2, seed=s).train(
        y="y", training_frame=fr) for s in (1, 2)]
    monkeypatch.setenv("H2O_TPU_SCORER_CACHE_MAX", "1")
    ev0 = MB.scorer_cache_stats()["evictions"]
    out0 = models[0].score_numpy(X)
    models[1].score_numpy(X)              # cap 1 → evicts models[0]
    assert MB.scorer_cache_stats()["evictions"] > ev0
    assert "_scorer_cache" not in models[0].__dict__
    # the evicted model still scores (cache recreated = a normal miss)
    m0 = MB.scorer_cache_stats()["misses"]
    out1 = models[0].score_numpy(X)
    assert MB.scorer_cache_stats()["misses"] > m0
    np.testing.assert_array_equal(out0, out1)


def test_select_rows_device_gather_parity(mesh8, monkeypatch):
    monkeypatch.setenv("H2O_TPU_DEVICE_GATHER_MIN", "0")
    rng = np.random.default_rng(9)
    n = 1000
    t0 = np.datetime64("2024-01-01T00:00:00", "ms")
    fr = h2o.Frame.from_arrays({
        "x": rng.normal(size=n).astype(np.float32),
        "c": np.array(["a", "b", "c"])[rng.integers(0, 3, size=n)],
        "t": t0 + rng.integers(0, 10 ** 9, size=n).astype(
            "timedelta64[ms]"),
    })
    idx = rng.permutation(n)[: 333]       # a CV-fold-like slice
    sub = fr.select_rows(idx)
    assert sub.nrows == 333
    np.testing.assert_array_equal(sub["x"].to_numpy(),
                                  fr["x"].to_numpy()[idx])
    np.testing.assert_array_equal(sub["c"].to_numpy(),
                                  fr["c"].to_numpy()[idx])
    assert sub["c"].domain == fr["c"].domain
    np.testing.assert_array_equal(sub["t"].to_numpy(),
                                  fr["t"].to_numpy()[idx])
    assert sub["t"].kind == "time"
    # negative indices normalize like numpy; out-of-range raises
    one = fr["x"].select_rows(np.array([-1]))
    assert one.to_numpy()[0] == fr["x"].to_numpy()[-1]
    with pytest.raises(IndexError):
        fr["x"].select_rows(np.array([n]))
    # float indices raise like numpy fancy-indexing, never truncate
    with pytest.raises(IndexError, match="integer"):
        fr["x"].select_rows(np.array([0.9, 2.7]))
    # empty selection stays on the host path and yields a 0-row Vec
    assert fr["x"].select_rows(np.array([], dtype=int)).nrows == 0
