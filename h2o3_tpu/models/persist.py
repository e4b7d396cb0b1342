"""Binary model save/load — full-fidelity, pickle-free.

Reference: ``hex/Model.java`` ``exportBinaryModel`` / ``importBinaryModel``
(the ``/3/Models/.../save`` + ``/99/Models.bin`` routes) built on the Iced
auto-serialization (``water/Iced.java:5-33``, javassist-woven ``$Icer``
delegates).

TPU-native replacement for Iced: a typed, allowlisted object-tree format.
Structure goes to JSON, numeric payloads to one npz, and object classes are
restricted to the ``h2o3_tpu`` package — loading reconstructs instances via
``__new__`` + field assignment and never executes arbitrary code (pickle's
``__reduce__`` hole is the reason the reference's own Grid import warns about
trusted files; this format has no such hole).

Handles every model class generically: dataclasses (params, DataInfo,
metrics), plain objects (BoostedTrees/Trees, models themselves), numpy
arrays, containers, enums, and shared references (memoized by object id so
aliased sub-objects stay aliased after load).
"""

from __future__ import annotations

import dataclasses
import importlib
import io
import json
import math
import os
import zipfile
from enum import Enum
from typing import Any, Dict, List, Optional, Union

import numpy as np

FORMAT_VERSION = 1

#: only classes inside these packages may be instantiated at load time
_ALLOWED_PREFIXES = ("h2o3_tpu.",)


# ---------------------------------------------------------------------------
# encode


class _Encoder:
    def __init__(self) -> None:
        self.arrays: Dict[str, np.ndarray] = {}
        self.memo: Dict[int, int] = {}  # id(obj) -> object table index
        self.next_ref = 0

    def enc(self, o: Any) -> Any:
        if o is None or isinstance(o, (bool, str)):
            return o
        if isinstance(o, (int, np.integer)):
            return int(o)
        if isinstance(o, (float, np.floating)):
            f = float(o)
            if math.isfinite(f):
                return f
            return {"__k": "f", "v": repr(f)}
        if isinstance(o, np.ndarray):
            aid = f"a{len(self.arrays)}"
            self.arrays[aid] = o
            return {"__k": "nd", "id": aid}
        if isinstance(o, (list, tuple)):
            return {
                "__k": "list" if isinstance(o, list) else "tuple",
                "items": [self.enc(x) for x in o],
            }
        if isinstance(o, dict):
            return {
                "__k": "dict",
                "items": [[self.enc(k), self.enc(v)] for k, v in o.items()],
            }
        if isinstance(o, Enum):
            return {
                "__k": "enum",
                "cls": f"{type(o).__module__}:{type(o).__qualname__}",
                "name": o.name,
            }
        if hasattr(o, "__dict__") or hasattr(o, "__slots__"):
            oid = id(o)
            if oid in self.memo:
                return {"__k": "ref", "ref": self.memo[oid]}
            self.memo[oid] = ref = self.next_ref
            self.next_ref += 1
            cls = type(o)
            mod = cls.__module__
            if not any(mod.startswith(p) or mod == p.rstrip(".") for p in _ALLOWED_PREFIXES):
                raise TypeError(
                    f"cannot serialize {cls.__module__}.{cls.__qualname__}: "
                    "outside the h2o3_tpu allowlist"
                )
            if hasattr(o, "__dict__"):
                fields = dict(vars(o))
            else:
                fields = {
                    s: getattr(o, s)
                    for s in cls.__slots__
                    if hasattr(o, s)
                }
            # device arrays / callables cannot ride a checkpoint
            clean = {}
            for k, v in fields.items():
                if callable(v) and not isinstance(v, type):
                    continue  # drop bound callables (monitors, caches)
                if k == "fit_eval":
                    # a fit's margins of its training frame, pinned to
                    # that live frame and dropped by the fit's own metrics
                    # call — process-local by construction, never persisted
                    continue
                clean[k] = v
            return {
                "__k": "obj",
                "id": ref,
                "cls": f"{mod}:{cls.__qualname__}",
                "fields": {k: self.enc(v) for k, v in clean.items()},
            }
        raise TypeError(f"cannot serialize {type(o)!r}")


# ---------------------------------------------------------------------------
# decode


class _Decoder:
    def __init__(self, arrays) -> None:
        self.arrays = arrays
        self.table: Dict[int, Any] = {}

    @staticmethod
    def _resolve(spec: str) -> type:
        mod, _, qual = spec.partition(":")
        if not any(mod.startswith(p) or mod == p.rstrip(".") for p in _ALLOWED_PREFIXES):
            raise ValueError(f"class {spec!r} outside the h2o3_tpu allowlist")
        m = importlib.import_module(mod)
        o: Any = m
        for part in qual.split("."):
            o = getattr(o, part)
        if not isinstance(o, type):
            raise ValueError(f"{spec!r} is not a class")
        return o

    def dec(self, e: Any) -> Any:
        if e is None or isinstance(e, (bool, int, float, str)):
            return e
        k = e["__k"]
        if k == "f":
            return float(e["v"])
        if k == "nd":
            return np.asarray(self.arrays[e["id"]])
        if k == "list":
            return [self.dec(x) for x in e["items"]]
        if k == "tuple":
            return tuple(self.dec(x) for x in e["items"])
        if k == "dict":
            return {self.dec(kk): self.dec(v) for kk, v in e["items"]}
        if k == "enum":
            return getattr(self._resolve(e["cls"]), e["name"])
        if k == "ref":
            return self.table[e["ref"]]
        if k == "obj":
            cls = self._resolve(e["cls"])
            obj = cls.__new__(cls)
            self.table[e["id"]] = obj
            for name, fe in e["fields"].items():
                object.__setattr__(obj, name, self.dec(fe))
            return obj
        raise ValueError(f"unknown node kind {k!r}")


# ---------------------------------------------------------------------------
# public API


def _write_archive(dest, model) -> None:
    """Write the zip(JSON tree + npz) container to a path or file object."""
    enc = _Encoder()
    tree = enc.enc(model)
    meta = {
        "version": FORMAT_VERSION,
        "algo": getattr(model, "algo_name", type(model).__name__),
        "class": f"{type(model).__module__}:{type(model).__qualname__}",
    }
    buf = io.BytesIO()
    np.savez_compressed(buf, **enc.arrays)
    with zipfile.ZipFile(dest, "w", zipfile.ZIP_DEFLATED) as z:
        # fixed entry timestamps: dumps_model of the same model is
        # byte-identical across calls and nodes, so the serving plane can
        # compare home/replica blob copies by digest
        for name, data in (("meta.json", json.dumps(meta)),
                           ("model.json", json.dumps(tree)),
                           ("arrays.npz", buf.getvalue())):
            info = zipfile.ZipInfo(name, date_time=(1980, 1, 1, 0, 0, 0))
            info.compress_type = zipfile.ZIP_DEFLATED
            info.external_attr = 0o600 << 16
            z.writestr(info, data)


def _read_archive(src):
    """Decode a container written by :func:`_write_archive`."""
    with zipfile.ZipFile(src, "r") as z:
        meta = json.loads(z.read("meta.json"))
        if meta.get("version", 0) > FORMAT_VERSION:
            raise ValueError(f"model file version {meta['version']} too new")
        tree = json.loads(z.read("model.json"))
        arrays = np.load(io.BytesIO(z.read("arrays.npz")), allow_pickle=False)
        return _Decoder(arrays).dec(tree)


def save_model(model, path: Union[str, os.PathLike]) -> str:
    """Serialize a trained model (any algo) to ``path``. Returns the path."""
    path = os.fspath(path)
    _write_archive(path, model)
    return path


def dumps_model(model) -> bytes:
    """The :func:`save_model` container as bytes — the wire form a
    distributed-search member ships a finished cell's model back in."""
    buf = io.BytesIO()
    _write_archive(buf, model)
    return buf.getvalue()


def loads_model(data: bytes, key: Optional[str] = None, register: bool = False):
    """Decode a :func:`dumps_model` blob.  ``register=False`` by default:
    the receiving side (cluster/search.py) must collision-check keys
    minted in another node's process before the model joins the DKV."""
    model = _read_archive(io.BytesIO(data))
    if not register:
        return model
    from h2o3_tpu.keyed import DKV

    if key:
        model.key = key
        DKV.put(key, model)
    elif getattr(model, "key", None):
        DKV.put(model.key, model)
    return model


def load_model(
    path: Union[str, os.PathLike], key: Optional[str] = None, register: bool = True
):
    """Load a model written by ``save_model`` and register it in the DKV.

    key: register under this key instead of the file's saved key — the saved
    key is then left untouched, so restoring a snapshot under a new id never
    clobbers a live model that happens to share the original key.
    register=False: decode only, touch nothing — callers that must
    type-check the payload first (a grid route handed a model file, or vice
    versa) register explicitly after checking."""
    from h2o3_tpu.keyed import DKV

    path = os.fspath(path)
    model = _read_archive(path)
    if not register:
        return model
    if key:
        model.key = key
        DKV.put(key, model)
    elif getattr(model, "key", None):
        DKV.put(model.key, model)
    return model
