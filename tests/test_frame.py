import numpy as np
import pytest

from h2o_kubernetes_tpu import Frame
from h2o_kubernetes_tpu.frame import NA_ENUM


def _frame(mesh8):
    rng = np.random.default_rng(1)
    x = rng.normal(2.0, 3.0, size=1000).astype(np.float32)
    x[::17] = np.nan
    cat = np.array(["a", "b", "c"])[rng.integers(0, 3, size=1000)]
    y = rng.integers(0, 2, size=1000).astype(np.float32)
    return Frame.from_arrays({"x": x, "cat": cat, "y": y}), x, cat


def test_shapes_and_names(mesh8):
    fr, x, cat = _frame(mesh8)
    assert fr.shape == (1000, 3)
    assert fr.names == ["x", "cat", "y"]
    assert fr["cat"].is_enum()
    assert fr["cat"].domain == ["a", "b", "c"]


def test_rollups_match_numpy(mesh8):
    fr, x, cat = _frame(mesh8)
    r = fr["x"].rollups()
    valid = x[~np.isnan(x)]
    np.testing.assert_allclose(r["mean"], valid.mean(), rtol=1e-4)
    np.testing.assert_allclose(r["sigma"], valid.std(ddof=1), rtol=1e-3)
    np.testing.assert_allclose(r["min"], valid.min(), rtol=1e-6)
    np.testing.assert_allclose(r["max"], valid.max(), rtol=1e-6)
    assert r["nacnt"] == int(np.isnan(x).sum())


def test_enum_roundtrip_and_na(mesh8):
    codes = np.array([0, 1, NA_ENUM, 2, 1], dtype=np.int32)
    fr = Frame.from_arrays({"c": codes}, domains={"c": ["x", "y", "z"]})
    v = fr["c"]
    assert v.nacnt() == 1
    assert v.cardinality() == 3
    back = v.to_numpy()
    np.testing.assert_array_equal(back, codes)


def test_to_matrix_and_mask(mesh8):
    fr, x, cat = _frame(mesh8)
    m = fr.to_matrix(["x", "y"])
    assert m.shape[1] == 2
    mask = fr.valid_mask()
    assert float(mask.sum()) == 1000


def test_subframe_drop(mesh8):
    fr, *_ = _frame(mesh8)
    assert fr[["x", "y"]].names == ["x", "y"]
    assert fr.drop("cat").names == ["x", "y"]


def test_to_pandas(mesh8):
    fr, x, cat = _frame(mesh8)
    df = fr.to_pandas()
    assert list(df.columns) == ["x", "cat", "y"]
    assert df["cat"].iloc[0] in ("a", "b", "c")


def test_explicit_domain_on_strings(mesh8):
    fr = Frame.from_arrays({"g": np.array(["b", "a", "zz", "b"])},
                           domains={"g": ["a", "b"]})
    np.testing.assert_array_equal(fr["g"].to_numpy(),
                                  [1, 0, NA_ENUM, 1])  # 'zz' not in domain


def test_na_tokens_are_categories(mesh8):
    fr = Frame.from_arrays({"g": np.array(["NA", "nan", "None", "x"])})
    assert fr["g"].nacnt() == 0
    assert "NA" in fr["g"].domain
    fr2 = Frame.from_arrays({"g": np.array(["a", None, float("nan"), ""],
                                           dtype=object)})
    assert fr2["g"].nacnt() == 3


def test_empty_selection_returns_empty(mesh8):
    fr, *_ = _frame(mesh8)
    assert fr.columns([]) == []


def test_time_column_precision(mesh8):
    t = np.array(["2026-07-29T00:00:00.123", "2026-07-29T00:00:01.456"],
                 dtype="datetime64[ms]")
    fr = Frame.from_arrays({"t": t})
    v = fr["t"]
    assert v.kind == "time"
    back = v.to_numpy()
    np.testing.assert_allclose(back[1] - back[0], 1333.0)  # exact ms delta
    r = v.rollups()
    np.testing.assert_allclose(r["max"] - r["min"], 1333.0)


def test_int_shard_padding(mesh8):
    from h2o_kubernetes_tpu.runtime import shard_rows
    xs = shard_rows(np.arange(13, dtype=np.int32))
    assert np.asarray(xs)[13:].tolist() == [-1, -1, -1]


def test_time_nat_is_na(mesh8):
    t = np.array(["2026-01-01", "NaT", "2026-01-02"], dtype="datetime64[ms]")
    v = Frame.from_arrays({"t": t})["t"]
    assert v.nacnt() == 1
    r = v.rollups()
    np.testing.assert_allclose(r["max"] - r["min"], 86400000.0)


def test_to_pandas_all_na_enum(mesh8):
    fr = Frame.from_arrays({"g": np.array([None, None], dtype=object)})
    df = fr.to_pandas()
    assert df["g"].isna().all()


def test_float_codes_with_nan(mesh8):
    fr = Frame.from_arrays({"c": np.array([0.0, np.nan, 1.0])},
                           domains={"c": ["a", "b"]})
    assert fr["c"].nacnt() == 1
    np.testing.assert_array_equal(fr["c"].to_numpy(), [0, NA_ENUM, 1])


# -- string columns through the code-point table (frame/encode.py) ----------

def _airports(rng, n=300):
    letters = np.array(list("ABCDEFGHIJKLMNOPQRSTUVWXYZ"))
    return np.unique(["".join(rng.choice(letters, size=rng.integers(3, 5)))
                      for _ in range(n)])


def _string_cases():
    rng = np.random.default_rng(7)
    pick = lambda levels, n=2000: np.array(levels)[  # noqa: E731
        rng.integers(0, len(levels), size=n)]
    wide = np.array([chr(0x100 + i) * 4 for i in range(40)])   # 41**4 keys
    table, sort = "factorize_table", "factorize"
    return {
        "u1_two_levels": (pick(["s", "b"]), table),
        "u1_with_na": (pick(["Y", "N", ""]), table),
        "u3_ragged": (pick(["NO", "YES"]), table),
        "u4_airports": (pick(_airports(rng), 20000), table),
        "u4_airports_with_na": (pick(list(_airports(rng)) + [""]), table),
        "non_ascii": (pick(["é", "ß", "日本", "\U0001d518x", "a", ""]),
                      table),
        "prefix_pair": (pick(["A", "AB"]), table),
        "nul_inside": (np.array(["a\0b", "a", "ab", "\0a", "a"]), table),
        "na_tokens_stay": (pick(["NA", "nan", "x", "None"]), table),
        "every_row_na": (np.array([""] * 11), table),
        "zero_rows": (np.array([], dtype="<U3"), table),
        "one_row": (np.array(["x"]), table),
        "strided": (pick(["a", "b", "c"])[::3], table),
        "bytes_ascii": (np.array([b"x", b"yy", b"", b"x", b"Yy"]), table),
        "past_the_cap": (wide[rng.integers(0, 40, size=500)], sort),
        "big_endian": (pick(["a", "bc"]).astype(">U2"), sort),
        "object_with_none": (
            np.array(["a", None, "b", float("nan"), "", "a"], dtype=object),
            sort),
    }


_STRING_CASES = _string_cases()


@pytest.mark.parametrize("arr,path", _STRING_CASES.values(),
                         ids=list(_STRING_CASES))
def test_table_factorize_is_the_sort(arr, path, monkeypatch):
    """The table path gives what the sort path gives, to the bit: the
    same int32 codes, the same domain in the same order; and each path
    serves what it should."""
    from h2o_kubernetes_tpu.frame import encode
    from h2o_kubernetes_tpu.frame.frame import _factorize

    codes, domain, took = _factorize(arr)
    assert took == path
    monkeypatch.setattr(encode, "factorize_table", lambda a: None)
    want_codes, want_domain, sorted_by = _factorize(arr)
    assert sorted_by == "factorize"
    assert codes.dtype == want_codes.dtype == np.int32
    np.testing.assert_array_equal(codes, want_codes)
    assert domain == want_domain and all(type(d) is str for d in domain)
    # and what the sort is held to: "" is the one NA, the domain sorted
    if arr.dtype.kind in "US":
        text = arr.astype(str)
        np.testing.assert_array_equal(codes == NA_ENUM, text == "")
        assert domain == sorted(set(text[text != ""].tolist()))


def test_table_path_in_a_frame(mesh8):
    y = np.array(["s", "b", "", "s", "b", "b", "s", "s", "b"])
    v = Frame.from_arrays({"y": y})["y"]
    assert v.domain == ["b", "s"] and v.nacnt() == 1
    np.testing.assert_array_equal(
        v.to_numpy(), [1, 0, NA_ENUM, 1, 0, 0, 1, 1, 0])


# -- a column in its storage dtype goes to the device as it is ---------------

def _aligned(values, dtype, align=64):
    """A copy of `values` whose buffer starts on an `align`-byte
    boundary: what the CPU backend's `jnp.asarray` keeps without a
    copy."""
    values = np.asarray(values, dtype=dtype)
    raw = np.empty(values.nbytes + align, dtype=np.uint8)
    start = -raw.ctypes.data % align
    out = raw[start:start + values.nbytes].view(dtype)
    out[:] = values
    return out


@pytest.mark.parametrize("aliases", [False, True])
def test_host_rows_copies_only_where_the_put_aliases(aliases, monkeypatch):
    from h2o_kubernetes_tpu.frame import Vec, frame

    monkeypatch.setattr(frame, "_put_aliases", lambda: aliases)
    f32 = np.arange(64, dtype=np.float32)
    i32 = np.arange(64, dtype=np.int32) % 3
    host, kind, _, pad = Vec._host_rows(f32)
    assert host.dtype == np.float32 and kind == "numeric" and np.isnan(pad)
    assert np.shares_memory(host, f32) is not aliases
    host, kind, _, pad = Vec._host_rows(i32, domain=["a", "b", "c"])
    assert host.dtype == np.int32 and kind == "enum" and pad == NA_ENUM
    assert np.shares_memory(host, i32) is not aliases
    # whatever needs converting is converted as before
    for x, domain in ((f32.astype(np.float64), None),
                      (i32.astype(np.int64), None),
                      (f32 > 7, None),
                      (i32.astype(np.int8), ["a", "b", "c"]),
                      (np.array([0.0, np.nan, 2.0]), ["a", "b", "c"])):
        host = Vec._host_rows(x, domain=domain)[0]
        assert host.dtype == (np.int32 if domain else np.float32)
        assert not np.shares_memory(host, x)
        np.testing.assert_array_equal(
            host, np.where(np.isnan(x), NA_ENUM, x) if domain else x)
    t = np.array(["2026-07-29", "2026-07-30"], dtype="datetime64[ms]")
    assert Vec._host_rows(t)[0].dtype == np.float32


@pytest.mark.parametrize("devices", [1, 8])
def test_frame_keeps_nothing_of_the_callers_buffer(devices, mesh8):
    """`from_arrays` / `from_numpy` hand a float32 (int32 with a domain)
    column on without converting it; the frame must not go on reading
    the caller's array. One device is where the CPU backend would alias
    an aligned buffer."""
    import jax

    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.frame import Vec

    n = 4096
    cols = {"f": _aligned(np.linspace(0, 1, n), np.float32),
            "c": _aligned(np.arange(n) % 3, np.int32),
            "strided": _aligned(np.arange(2 * n), np.float32)[::2],
            "d": _aligned(np.linspace(0, 1, n), np.float64)}
    want = {k: v.copy() for k, v in cols.items()}
    with h2o.use_mesh(h2o.make_mesh(devices=jax.devices()[:devices])):
        fr = Frame.from_arrays(cols, domains={"c": ["a", "b", "c"]})
        vecs = {k: fr.vec(k) for k in cols}
        vecs["from_numpy"] = Vec.from_numpy(cols["f"])
        want["from_numpy"] = want["f"]
        jax.block_until_ready([v.data for v in vecs.values()])
        for a in cols.values():
            a[...] = 7
        for k, v in vecs.items():
            np.testing.assert_array_equal(
                v.to_numpy(), want[k].astype(v.data.dtype), err_msg=k)


def test_transfers_are_waited_for_one_behind(monkeypatch, mesh8):
    """`from_arrays` queues a column, then waits for the one before it,
    and for the last before it returns: the caller's arrays have been
    read, and two columns at most are on their way. `from_numpy` waits
    where what it put is the caller's own array."""
    from h2o_kubernetes_tpu.frame import Vec, frame

    events = []
    put, ready = frame.put_rows, frame.jax.block_until_ready
    names = {}

    def put_rows(host, *a):
        data = put(host, *a)
        names[id(data)] = f"c{len(names)}"
        events.append("put " + names[id(data)])
        return data

    def block_until_ready(data):
        events.append("wait " + names.get(id(data), "?"))
        return ready(data)

    monkeypatch.setattr(frame, "put_rows", put_rows)
    monkeypatch.setattr(frame.jax, "block_until_ready", block_until_ready)
    fr = Frame.from_arrays({"a": np.arange(8.0), "b": np.array(["x", "y"] * 4),
                            "c": np.arange(8, dtype=np.float32)})
    assert events == ["put c0", "put c1", "wait c0", "put c2", "wait c1",
                      "wait c2"]
    np.testing.assert_array_equal(fr["c"].to_numpy(), np.arange(8))
    assert Frame.from_arrays({}).names == []

    monkeypatch.setattr(frame, "shard_rows", lambda host, pad_value: put(host))
    x = np.arange(8, dtype=np.float32)
    for aliases, given, waits in ((False, x, 1), (True, x, 0),
                                  (False, x.astype(np.float64), 0)):
        del events[:]
        monkeypatch.setattr(frame, "_put_aliases", lambda: aliases)
        np.testing.assert_array_equal(Vec.from_numpy(given).to_numpy(), x)
        assert len(events) == waits
