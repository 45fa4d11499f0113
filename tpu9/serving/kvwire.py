"""Serializable paged-KV blocks (ISSUE 16): one versioned wire format
powering disaggregated prefill/decode, drain migration and block-ship
failover resume.

The ``.tpu9w`` v1/v2 discipline applied to KV: a payload either parses
completely against a version this reader knows, or fails loudly BEFORE
any pool mutation — never a mid-import KeyError with half a prefix
spliced into the cache.

Format v1 (little-endian)::

    magic    b"TPU9KV\\0"          7 bytes
    version  u16                   = 1
    hlen     u32                   header JSON byte length
    header   JSON (utf-8)
    planes   raw plane bytes, concatenated in header["planes"] order

Header fields:

- geometry: ``n_layers`` (the depth of the pool, ``cfg.kv_layers``: a looped
  decoder keeps a plane per pass and layer), ``kv_block_size``, ``n_kv_heads``,
  ``head_dim``, ``kv_dtype`` ("bfloat16" | "int8" | ...) — must match
  the importing pool exactly (block ids are meaningless across
  geometries);
- ``n_blocks`` / ``n_tokens`` / ``prefix_key`` (hex of the block-aligned
  token prefix's key — the sha1 of its tokens as 64-bit little-endian
  integers, made by :func:`tpu9.utils.prefixkey.prefix_keys`, the key's one
  owner, which ``PrefixCache`` and the router share) — what the importer
  adopts into its prefix cache. The key is compared with keys the same code
  made in the same deployment and never persisted: a payload from another
  version of the key adopts under a name no lookup asks for, and ages out;
- ``topology`` (``policy.describe()``) — informational: planes are
  always CANONICAL full-head arrays (``[L, nb, BS, KH, D]`` payload,
  ``[L, nb, BS, KH]`` f32 scales), because export gathers head shards
  through the shard policy and import re-places through it. A tp=2
  exporter and a tp=1 importer interoperate byte-for-byte;
- ``planes``: ordered ``{name, dtype, shape, nbytes}`` records.

Transport is NOT this module's business: payloads ride the existing
``CacheClient`` hedged-read path under the ``kv:`` namespace
(content-addressed — peer verification requires plain chunk digests).
BND001 restricts importers to kvpool/engine/runner/cache/bench: the
router and gateway speak policy (flags, keys, token counts), never
payloads.
"""

from __future__ import annotations

import json
import struct

import numpy as np

MAGIC = b"TPU9KV\x00"
FORMAT_VERSION = 1
# cache-plane namespace prefix for shipped blocks (the digest itself
# stays a plain content hash — hedged peer reads verify it)
KV_NAMESPACE = "kv"

_PRELUDE = struct.Struct("<7sHI")          # magic, version, header length

# plane dtypes this reader will materialize. An unlisted dtype in a
# well-formed v1 header is a forward-compat failure, reported as such.
_DTYPES = ("bfloat16", "float32", "float16", "int8", "int32")


class KvWireError(ValueError):
    """Malformed / unsupported / geometry-mismatched KV payload."""


def _np_dtype(name: str) -> np.dtype:
    if name not in _DTYPES:
        raise KvWireError(f"kvwire: unsupported plane dtype {name!r} "
                          f"(supported: {', '.join(_DTYPES)})")
    if name == "bfloat16":
        import jax.numpy as jnp
        return np.dtype(jnp.bfloat16)
    return np.dtype(name)


def geometry(cfg, ecfg, kv_quant: bool) -> dict:
    """The pool-identity fields import refuses to cross."""
    return {"n_layers": int(cfg.kv_layers),
            "kv_block_size": int(ecfg.kv_block_size),
            "n_kv_heads": int(cfg.n_kv_heads),
            "head_dim": int(cfg.head_dim),
            "kv_dtype": "int8" if kv_quant else np.dtype(cfg.dtype).name}


def check_geometry(header: dict, geo: dict) -> None:
    """Every mismatch in one error — a cross-deployment ship failure
    should read like a diff, not a scavenger hunt."""
    bad = [f"{k}: payload={header.get(k)!r} pool={v!r}"
           for k, v in geo.items() if header.get(k) != v]
    if bad:
        raise KvWireError("kvwire: pool geometry mismatch ("
                          + "; ".join(bad) + ")")


def encode_blocks(meta: dict, planes: dict[str, np.ndarray]) -> bytes:
    """``meta`` (geometry + prefix metadata + topology) + canonical
    host planes → one self-describing payload."""
    header = dict(meta)
    records = []
    blobs = []
    for name, arr in planes.items():
        arr = np.ascontiguousarray(arr)
        raw = arr.tobytes()
        records.append({"name": name, "dtype": arr.dtype.name,
                        "shape": list(arr.shape), "nbytes": len(raw)})
        blobs.append(raw)
    header["planes"] = records
    hjson = json.dumps(header, sort_keys=True).encode()
    return b"".join([_PRELUDE.pack(MAGIC, FORMAT_VERSION, len(hjson)),
                     hjson] + blobs)


def decode_header(data: bytes) -> tuple[dict, int]:
    """(header, plane-bytes offset). Version/shape gates live here so
    both full decodes and header-only peeks fail identically."""
    if len(data) < _PRELUDE.size:
        raise KvWireError(f"kvwire: payload truncated at {len(data)} "
                          f"bytes (prelude is {_PRELUDE.size})")
    magic, version, hlen = _PRELUDE.unpack_from(data)
    if magic != MAGIC:
        raise KvWireError("kvwire: bad magic (not a KV block payload)")
    if version != FORMAT_VERSION:
        raise KvWireError(
            f"kvwire: unsupported format version {version} (this reader "
            f"speaks v{FORMAT_VERSION}; refusing to guess at a newer "
            "layout)")
    off = _PRELUDE.size + hlen
    if len(data) < off:
        raise KvWireError("kvwire: payload truncated inside header")
    try:
        header = json.loads(data[_PRELUDE.size:off])
    except ValueError as exc:
        raise KvWireError(f"kvwire: undecodable header: {exc}") from exc
    if not isinstance(header, dict) or not isinstance(
            header.get("planes"), list):
        raise KvWireError("kvwire: header is not a plane-table dict")
    return header, off


def decode_blocks(data: bytes) -> tuple[dict, dict[str, np.ndarray]]:
    """Payload → (header, canonical host planes). Fully validated:
    every plane present, sized and shaped before anything is returned."""
    header, off = decode_header(data)
    planes: dict[str, np.ndarray] = {}
    for rec in header["planes"]:
        try:
            name, nbytes = rec["name"], int(rec["nbytes"])
            shape = tuple(int(d) for d in rec["shape"])
            dt = _np_dtype(str(rec["dtype"]))
        except (KeyError, TypeError, ValueError) as exc:
            raise KvWireError(
                f"kvwire: malformed plane record {rec!r}: {exc}") from exc
        if len(data) < off + nbytes:
            raise KvWireError(f"kvwire: plane {name!r} truncated")
        arr = np.frombuffer(data[off:off + nbytes], dtype=dt)
        if arr.size != int(np.prod(shape)):
            raise KvWireError(
                f"kvwire: plane {name!r} has {arr.size} elements, "
                f"shape {shape} needs {int(np.prod(shape))}")
        planes[name] = arr.reshape(shape)
        off += nbytes
    return header, planes
