"""Persistence — model/frame save & load (local filesystem).

Reference: water/persist/* (SURVEY.md §2b C20) provides binary model
save/load and frame export over pluggable backends (local/S3/HDFS/GCS);
h2o.save_model / h2o.load_model / h2o.export_file are the client verbs
(h2o-py). Built-in backends: local FS, mem:// (in-process object
store), read-only http(s)://, and the cloud stores s3:// gs:// hdfs://
(persist_cloud.py — stdlib REST clients, no SDK required); more can
register via PERSIST_SCHEMES (the reference's PersistManager registry).

Device arrays are converted to host numpy on save (a model file is
readable on any backend — the reference's binary models are likewise
cluster-independent), and flow back to device lazily on first use.
"""

from __future__ import annotations

import io
import json
import os
import pickle
from typing import Any, Callable

import numpy as np

__all__ = ["save_model", "load_model", "export_file", "save_frame",
           "load_frame", "PERSIST_SCHEMES", "read_bytes", "write_bytes",
           "write_bytes_atomic", "list_names", "is_remote", "join_path"]

_MAGIC = b"H2OTPU1\n"

# scheme -> (reader: path->bytes, writer: path,bytes->None) — the
# PersistManager registry (water/persist/PersistManager [U3]). Built-ins:
# bare paths (local FS), mem:// (in-process object store — the DKV-style
# scratch space), http(s):// (read-only remote fetch, the analog of the
# reference's PersistHTTP importFiles path). S3/GCS/HDFS register here
# the same way when their client libraries are present.
PERSIST_SCHEMES: dict[str, tuple[Callable, Callable]] = {}

_MEM_STORE: dict[str, bytes] = {}


def _mem_read(path: str) -> bytes:
    if path not in _MEM_STORE:
        raise FileNotFoundError(path)
    return _MEM_STORE[path]


def _mem_write(path: str, data: bytes) -> None:
    _MEM_STORE[path] = data


def _http_read(path: str) -> bytes:
    # one transient classifier for every persist HTTP verb: retries
    # 429/5xx (honoring Retry-After)/timeouts/resets/truncation, maps
    # 404 on this read to FileNotFoundError, fires the persist.http
    # fault point
    from .persist_cloud import _http

    return _http("GET", path)


def _http_write(path: str, data: bytes) -> None:
    raise ValueError("http(s):// is a read-only persist backend")


PERSIST_SCHEMES["mem"] = (_mem_read, _mem_write)
PERSIST_SCHEMES["http"] = (_http_read, _http_write)
PERSIST_SCHEMES["https"] = (_http_read, _http_write)

# cloud backends (s3/gs/hdfs) — stdlib REST clients, no SDK needed
from . import persist_cloud as _persist_cloud  # noqa: E402

_persist_cloud.register(PERSIST_SCHEMES)


def _write_bytes(path: str, data: bytes) -> None:
    scheme = path.split("://", 1)[0] if "://" in path else ""
    if scheme:
        if scheme not in PERSIST_SCHEMES:
            raise ValueError(f"no persist backend for scheme "
                             f"'{scheme}://' (register in PERSIST_SCHEMES)")
        PERSIST_SCHEMES[scheme][1](path, data)
        return
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "wb") as f:
        f.write(data)


def _read_bytes(path: str) -> bytes:
    scheme = path.split("://", 1)[0] if "://" in path else ""
    if scheme:
        if scheme not in PERSIST_SCHEMES:
            raise ValueError(f"no persist backend for scheme "
                             f"'{scheme}://'")
        return PERSIST_SCHEMES[scheme][0](path)
    with open(path, "rb") as f:
        return f.read()


# public raw-bytes surface so other subsystems (AutoML checkpoints,
# REST export) stay backend-agnostic without reaching into privates
read_bytes = _read_bytes
write_bytes = _write_bytes


def write_bytes_atomic(path: str, data: bytes,
                       verify: bool = True) -> None:
    """Crash-safe write: readers see the OLD bytes or the NEW bytes,
    never a torn prefix.

    Local FS: write-temp in the same directory + fsync + os.replace
    (the rename is atomic on POSIX), so a process killed mid-write can
    never leave a half-written file at `path` — the durable PoolStore
    and the registry index both depend on this (a corrupted index
    would break every subsequent fetch). Scheme backends (mem://,
    s3://...) already replace whole objects, so they take the plain
    write. ``verify`` reads the bytes back and compares digests — a
    cheap end-to-end check that the backend stored what it was given.
    """
    import hashlib

    if "://" in path:
        _write_bytes(path, data)
    else:
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = os.path.join(d, f".{os.path.basename(path)}."
                              f"{os.getpid()}.tmp")
        try:
            with open(tmp, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                try:
                    os.remove(tmp)
                except OSError:
                    pass
    if verify:
        got = _read_bytes(path)
        if hashlib.sha256(got).digest() != \
                hashlib.sha256(data).digest():
            raise IOError(
                f"atomic write to {path} did not read back intact "
                f"({len(got)} bytes back vs {len(data)} written)")


def list_names(base: str) -> list[str]:
    """Child object names directly under a local dir or a mem://
    prefix (the two backends the durable PoolStore supports); other
    schemes have no cheap listing and return []. Missing dir = []."""
    if not is_remote(base):
        try:
            return sorted(
                n for n in os.listdir(base)
                if os.path.isfile(os.path.join(base, n)))
        except (FileNotFoundError, NotADirectoryError):
            return []
    if base.startswith("mem://"):
        prefix = base.rstrip("/") + "/"
        out = set()
        for key in list(_MEM_STORE):
            if key.startswith(prefix):
                rest = key[len(prefix):]
                if rest and "/" not in rest:
                    out.add(rest)
        return sorted(out)
    return []


def is_remote(path: str) -> bool:
    """True when `path` routes through a PERSIST_SCHEMES backend."""
    return "://" in path


def join_path(base: str, name: str) -> str:
    """Join a child name onto a local dir or a scheme://-addressed one."""
    if is_remote(base):
        return base.rstrip("/") + "/" + name
    return os.path.join(base, name)


class _HostPickler(pickle.Pickler):
    """Pickler that lands every jax.Array as host numpy."""

    def persistent_id(self, obj):
        import jax

        if isinstance(obj, jax.Array):
            return ("jax_array", np.asarray(obj))
        return None


# modules a model file may legitimately reference: this package, numpy
# internals, and stdlib builders of plain containers. Everything else —
# os, subprocess, builtins beyond the basics — is refused, so a
# tampered model file cannot execute arbitrary code via a crafted
# GLOBAL opcode (the classic pickle RCE).
_SAFE_MODULE_PREFIXES = ("h2o_kubernetes_tpu.",)
_SAFE_GLOBALS = {
    ("builtins", "dict"), ("builtins", "list"), ("builtins", "tuple"),
    ("builtins", "set"), ("builtins", "frozenset"), ("builtins", "int"),
    ("builtins", "float"), ("builtins", "str"), ("builtins", "bytes"),
    ("builtins", "bool"), ("builtins", "complex"), ("builtins", "slice"),
    ("builtins", "bytearray"),
    ("collections", "OrderedDict"), ("collections", "defaultdict"),
    ("numpy", "ndarray"), ("numpy", "dtype"),
    ("numpy._core.multiarray", "_reconstruct"),
    ("numpy.core.multiarray", "_reconstruct"),
    ("numpy._core.multiarray", "scalar"),
    ("numpy.core.multiarray", "scalar"),
    ("numpy._core.numeric", "_frombuffer"),
    ("numpy.core.numeric", "_frombuffer"),
    ("_codecs", "encode"),
}


class _HostUnpickler(pickle.Unpickler):
    def persistent_load(self, pid):
        tag, val = pid
        if tag == "jax_array":
            return val          # numpy; flows back to device on first use
        raise pickle.UnpicklingError(f"unknown persistent id {tag!r}")

    def find_class(self, module, name):
        if (module, name) in _SAFE_GLOBALS:
            return super().find_class(module, name)
        if module.startswith(_SAFE_MODULE_PREFIXES):
            obj = super().find_class(module, name)
            # CLASSES defined in this package only: a bare module-prefix
            # rule would also hand back re-exported imports (os, json)
            # and package-level functions callable with attacker args
            if isinstance(obj, type) and getattr(
                    obj, "__module__", "").startswith(
                    _SAFE_MODULE_PREFIXES):
                return obj
        raise pickle.UnpicklingError(
            f"model file references {module}.{name}, which is outside "
            "the allowed model-class set — refusing to load (possible "
            "tampering; use MOJO artifacts for untrusted scoring)")


def save_model(model, path: str, force: bool = True) -> str:
    """h2o.save_model analog: binary model file at `path`.

    If `path` has no extension it is treated as a directory and the
    file is named <algo>.model inside it (h2o-py's directory behavior).
    """
    if not force and os.path.exists(path):
        raise FileExistsError(path)
    if "://" not in path and not os.path.splitext(path)[1]:
        path = os.path.join(path, f"{model.algo}.model")
    buf = io.BytesIO()
    buf.write(_MAGIC)
    _HostPickler(buf, protocol=pickle.HIGHEST_PROTOCOL).dump(model)
    _write_bytes(path, buf.getvalue())
    return path


def load_model(path: str):
    """h2o.load_model analog.

    Trust model: binary model files are pickle-based (like the
    reference's binary models, they are for same-owner save/restore
    only), but the loader REFUSES any class outside this package /
    numpy container internals (`_HostUnpickler.find_class`), so a
    tampered file cannot reach os/subprocess/arbitrary constructors.
    Defense in depth, not a sandbox — for artifacts that must cross a
    real trust boundary use the MOJO path (mojo.py), whose npz+JSON
    format is data-only.
    """
    data = _read_bytes(path)
    if not data.startswith(_MAGIC):
        raise ValueError(f"{path} is not an h2o_kubernetes_tpu model file")
    model = _HostUnpickler(io.BytesIO(data[len(_MAGIC):])).load()
    trees = getattr(model, "trees", None)
    if trees is not None and getattr(trees, "cover", 1) is None:
        # model was saved before Tree grew the cover field (r2): backfill
        # a sentinel so predict/varimp work; predict_contributions
        # detects the all-NaN cover and asks for a re-train
        model.trees = trees._replace(
            cover=np.full_like(np.asarray(trees.value), np.nan))
    # a Tree pickled before it had `left_bins` (seven fields) loads
    # with the field's default, None: no set splits, which is what
    # every such model has (tests/test_set_splits.py)
    return model


def export_file(frame, path: str, header: bool = True,
                sep: str = ",") -> str:
    """h2o.export_file analog: write a Frame as CSV (local or scheme)."""
    from .frame.frame import NA_ENUM

    cols = []
    for name in frame.names:
        v = frame.vec(name)
        if v.is_enum():
            codes = v.to_numpy()
            dom = np.array(list(v.domain) + [""], dtype=object)
            col = dom[np.where(codes < 0, len(dom) - 1, codes)]
        elif v.kind == "time":
            ms = v.to_numpy()
            col = np.array(
                [np.datetime64(int(m), "ms").astype(str) if m == m else ""
                 for m in ms], dtype=object)
        else:
            x = v.to_numpy()
            col = np.where(np.isnan(x), "",
                           np.char.mod("%g", np.nan_to_num(x)))
        cols.append(col.astype(object))
    out = io.StringIO()
    if header:
        out.write(sep.join(frame.names) + "\n")
    quoted = []
    for c in cols:
        # RFC 4180: embedded quotes double inside a quoted field
        q = np.array(
            [f'"{str(s).replace(chr(34), chr(34) * 2)}"'
             if (sep in str(s) or '"' in str(s) or "\n" in str(s))
             else str(s) for s in c], dtype=object)
        quoted.append(q)
    for i in range(frame.nrows):
        out.write(sep.join(str(q[i]) for q in quoted) + "\n")
    _write_bytes(path, out.getvalue().encode())
    return path


def save_frame(frame, path: str) -> str:
    """Binary frame save (npz of columns + metadata) — the analog of the
    reference's distributed frame snapshot in the persist layer."""
    arrays: dict[str, np.ndarray] = {}
    meta: dict[str, Any] = {"names": frame.names, "kinds": {},
                            "domains": {}, "origins": {}}
    for name in frame.names:
        v = frame.vec(name)
        arrays[f"col_{name}"] = v.to_numpy()
        meta["kinds"][name] = v.kind
        if v.domain is not None:
            meta["domains"][name] = list(v.domain)
        if v.kind == "time":
            meta["origins"][name] = v.origin
    buf = io.BytesIO()
    # JSON, not pickle: frame files stay data-only so load_frame is safe
    # on untrusted input (matching the reference's data-only formats)
    meta_bytes = json.dumps(meta).encode()
    np.savez_compressed(buf, __meta__=np.frombuffer(
        meta_bytes, dtype=np.uint8), **arrays)
    _write_bytes(path, buf.getvalue())
    return path


def load_frame(path: str):
    from .frame import Frame, Vec

    with np.load(io.BytesIO(_read_bytes(path)), allow_pickle=False) as z:
        try:
            meta = json.loads(z["__meta__"].tobytes().decode())
        except (UnicodeDecodeError, json.JSONDecodeError):
            raise ValueError(
                f"{path}: frame metadata is not JSON — this looks like a "
                f"frame saved by a pre-0.2 build (pickle metadata); "
                f"re-export it with export_file/save_frame") from None
        vecs = {}
        for name in meta["names"]:
            arr = z[f"col_{name}"]
            kind = meta["kinds"][name]
            if kind == "time":
                # to_numpy returned absolute epoch-ms float64
                vecs[name] = Vec.from_numpy(arr, name, kind="time")
            elif kind == "enum":
                vecs[name] = Vec.from_numpy(
                    arr.astype(np.int32), name,
                    domain=meta["domains"][name], kind="enum")
            else:
                vecs[name] = Vec.from_numpy(arr, name)
    return Frame(vecs)
