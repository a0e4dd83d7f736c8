"""Model snapshot wire format — the payload of a FRAME_SNAPSHOT push.

One self-describing byte string per snapshot: an 8-byte magic, a
length-prefixed JSON meta record (model family, constructor config, leaf
manifest, optional binner cuts manifest, sequence number), then the raw
leaf bytes back-to-back in manifest order.  The same flat-dict params
shape every model family uses (``init()`` output / checkpoint.py leaves)
serializes without a treedef; the binner rides along as its cuts array +
constructor knobs so ``cuts_digest()`` survives the round trip exactly.

The snapshot's identity is :func:`snapshot_digest` — sha256 over the full
payload, truncated to 16 hex chars like ``QuantileBinner.cuts_digest``.
A receiver recomputes it before touching the model pointer; a torn or
corrupted push can only ever be rejected, never half-applied.
"""
from __future__ import annotations

import hashlib
import json
import struct
from typing import Optional, Tuple

import numpy as np

import jax.numpy as jnp

MAGIC = b"DTSNAP01"
_U32 = struct.Struct("<I")

#: model family name -> constructor (resolved lazily to keep import cost
#: off the protocol path)
_FAMILIES = ("linear", "fm", "ffm", "gbdt")


def _family_cls(family: str):
    from .. import models
    table = {
        "linear": models.SparseLinearModel,
        "fm": models.FactorizationMachine,
        "ffm": models.FieldAwareFactorizationMachine,
        "gbdt": models.GBDT,
    }
    if family not in table:
        raise ValueError(f"unknown model family '{family}' "
                         f"(expected one of {_FAMILIES})")
    return table[family]


def snapshot_digest(data: bytes) -> str:
    """16-hex content digest of a packed snapshot payload."""
    return hashlib.sha256(bytes(data)).hexdigest()[:16]


def pack_snapshot(family: str, config: dict, params: dict,
                  binner=None, seq: int = 0) -> bytes:
    """Serialize (family, constructor config, flat params dict[, binner])
    into one snapshot payload.  ``config`` must be the keyword arguments
    that rebuild the model object (JSON-serializable); ``params`` a flat
    dict of arrays/scalars (every family's ``init()`` shape; an optimizer's
    nested state is dropped, a gated model's flat ``count`` table rides)."""
    _family_cls(family)  # validate early, before any bytes move
    from ..models.common import RULE_STATE_KEYS
    manifest = []
    blobs = []
    for key in sorted(params):
        v = params[key]
        if v is None:
            manifest.append({"key": key, "kind": "none"})
            continue
        if isinstance(v, dict) and key in RULE_STATE_KEYS:
            continue    # training's state: scoring reads weights (and counts)
        if isinstance(v, dict):
            raise ValueError(f"params['{key}'] is nested; snapshots carry "
                             "flat param dicts only")
        a = np.ascontiguousarray(np.asarray(v))
        if a.dtype == object:
            raise ValueError(f"params['{key}'] is not an array")
        manifest.append({"key": key, "kind": "array",
                         "dtype": a.dtype.str, "shape": list(a.shape)})
        blobs.append(a.tobytes())
    meta = {"version": 1, "family": family, "config": dict(config),
            "seq": int(seq), "leaves": manifest}
    if binner is not None:
        if binner.cuts is None:
            raise ValueError("binner must be fitted before snapshotting")
        cuts = np.ascontiguousarray(np.asarray(binner.cuts, np.float32))
        meta["binner"] = {"num_bins": binner.num_bins,
                          "missing_aware": binner.missing_aware,
                          "cuts_shape": list(cuts.shape)}
        blobs.append(cuts.tobytes())
    head = json.dumps(meta, sort_keys=True).encode()
    return b"".join([MAGIC, _U32.pack(len(head)), head] + blobs)


def unpack_snapshot(data) -> Tuple[str, dict, dict, Optional[object]]:
    """Decode a snapshot payload -> ``(family, config, params, binner)``.

    Params come back as jnp arrays (0-d leaves stay 0-d, exactly what the
    predict paths consume); the binner, when present, is a fitted
    ``QuantileBinner`` whose ``cuts_digest()`` matches the training-side
    one bit for bit."""
    data = bytes(data)
    if data[:len(MAGIC)] != MAGIC:
        raise ValueError("not a model snapshot (bad magic)")
    off = len(MAGIC)
    (head_len,) = _U32.unpack_from(data, off)
    off += _U32.size
    meta = json.loads(data[off:off + head_len].decode())
    off += head_len
    if meta.get("version") != 1:
        raise ValueError(f"unsupported snapshot version {meta.get('version')}")
    params = {}
    for leaf in meta["leaves"]:
        if leaf["kind"] == "none":
            params[leaf["key"]] = None
            continue
        dt = np.dtype(leaf["dtype"])
        shape = tuple(leaf["shape"])
        count = int(np.prod(shape, dtype=np.int64)) if shape else 1
        n = dt.itemsize * count
        if count:
            a = np.frombuffer(data, dt, count=count,
                              offset=off).reshape(shape)
        else:
            a = np.zeros(shape, dt)
        off += n
        params[leaf["key"]] = jnp.asarray(a)
    binner = None
    if "binner" in meta:
        from ..models import QuantileBinner
        b = meta["binner"]
        shape = tuple(b["cuts_shape"])
        n = 4 * int(np.prod(shape, dtype=np.int64))
        cuts = np.frombuffer(data, np.float32,
                             count=n // 4, offset=off).reshape(shape)
        off += n
        binner = QuantileBinner(num_bins=b["num_bins"],
                                missing_aware=b["missing_aware"])
        binner.cuts = jnp.asarray(cuts)
    if off != len(data):
        raise ValueError(f"snapshot payload has {len(data) - off} "
                         "trailing bytes (torn write?)")
    return meta["family"], meta["config"], params, binner
