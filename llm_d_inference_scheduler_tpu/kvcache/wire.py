"""A KV handoff in bytes: what one engine serves and another accepts.

A body is K then V, each a C-order array of blocks gathered out of a pool
(``pages.gather_blocks``: ``[n_layers, n, block, n_kv_heads, head_dim]``) in
the pool's dtype; the headers beside it say its shape and dtype. A whole
export travels as one body, or as chunks of consecutive blocks that the
receiver joins. What arrives comes from another process, so :func:`validate`
holds it to the receiver's own geometry before anything is scattered.
"""

from __future__ import annotations

import json
from typing import Any, Mapping, Sequence

import jax.numpy as jnp
import numpy as np

from .pages import PageGeometry

H_SHAPE = "x-kv-shape"
H_CHUNK_SHAPE = "x-kv-chunk-shape"
H_DTYPE = "x-kv-dtype"
H_NUM_BLOCKS = "x-kv-num-blocks"
H_REAL_BLOCKS = "x-kv-real-blocks"
H_SEQ_LEN = "x-kv-seq-len"

# Device-pull byte accounting: kv_shape is the staged K array's shape, K and
# V move together, and kv_dtype names the element type.
_DTYPE_BYTES = {"float32": 4, "float16": 2, "bfloat16": 2, "int8": 1,
                "float8_e4m3fn": 1, "float8_e5m2": 1}


def param_bytes(ktp: Mapping[str, Any]) -> int | None:
    """Bytes a device-wire pull moves, derived from the exporter's staged
    geometry (the host path counts the payload directly)."""
    shape = ktp.get("kv_shape")
    if not shape:
        return None
    n = 1
    for d in shape:
        n *= int(d)
    return 2 * n * _DTYPE_BYTES.get(str(ktp.get("kv_dtype", "")), 2)


def validate(geom: PageGeometry, shape: Sequence[int], seq_len: int,
             real_nb: int | None, n_alloc: int) -> tuple[int, int]:
    """Hold an export's shape to this engine's geometry; returns (padded,
    real) block counts. ``shape``'s block dim may be pow2-PADDED (staging
    pads so gather/scatter compile counts stay bounded); ``real_nb`` (None:
    no padding) is the un-padded count that must fit the ``n_alloc`` blocks
    allocated for it."""
    if len(shape) != 5:
        raise ValueError(f"bad kv shape {shape}")
    L, nb, block, Hkv, Dh = shape
    if real_nb is None:
        real_nb = nb
    if (L, block, Hkv, Dh) != (geom.n_layers, geom.block, geom.n_kv_heads,
                               geom.head_dim):
        raise ValueError(f"kv geometry mismatch: {shape} vs model "
                         f"(L={geom.n_layers}, block={geom.block}, "
                         f"Hkv={geom.n_kv_heads}, Dh={geom.head_dim})")
    if not (0 < real_nb <= nb):
        raise ValueError(f"real block count {real_nb} outside padded {nb}")
    if nb > geom.max_blocks_per_seq or real_nb > n_alloc:
        raise ValueError(f"{real_nb}/{nb} exported blocks exceed budget "
                         f"(maxB={geom.max_blocks_per_seq}, alloc={n_alloc})")
    if not (0 < seq_len <= real_nb * block):
        raise ValueError(f"kv seq_len {seq_len} outside exported blocks")
    return nb, real_nb


def encode(k, v, *, real_blocks: int | None = None,
           chunk: bool = False) -> tuple[bytes, dict[str, str]]:
    """A gathered pair as (body, geometry headers): a whole export, whose
    block count may be padded past ``real_blocks``, or one ``chunk`` of it."""
    k, v = np.asarray(k), np.asarray(v)
    body = k.tobytes() + v.tobytes()
    if chunk:
        return body, {H_CHUNK_SHAPE: json.dumps(list(k.shape)),
                      H_DTYPE: str(k.dtype)}
    return body, {H_NUM_BLOCKS: str(k.shape[1]),
                  H_REAL_BLOCKS: str(real_blocks or k.shape[1]),
                  H_DTYPE: str(k.dtype),
                  H_SHAPE: json.dumps(list(k.shape))}


def _halves(body: bytes, shape: Sequence[int], dtype) -> tuple[np.ndarray,
                                                                np.ndarray]:
    expected = 2 * int(np.prod(shape)) * dtype.itemsize
    if len(body) != expected:
        raise ValueError(f"kv payload size {len(body)} != expected {expected}")
    half = len(body) // 2
    return (np.frombuffer(body[:half], dtype=dtype).reshape(shape),
            np.frombuffer(body[half:], dtype=dtype).reshape(shape))


def decode(geom: PageGeometry, headers: Mapping[str, str], body: bytes,
           n_alloc: int) -> tuple[np.ndarray, np.ndarray, int, int]:
    """A whole export as served -> (k, v, seq_len, real block count), checked
    against ``geom`` and the ``n_alloc`` blocks that wait for it. Raises
    ValueError / KeyError on anything malformed."""
    shape = tuple(int(x) for x in json.loads(headers[H_SHAPE]))
    seq_len = int(headers[H_SEQ_LEN])
    dtype = jnp.dtype(headers[H_DTYPE])
    _, real_nb = validate(geom, shape, seq_len,
                          int(headers.get(H_REAL_BLOCKS) or 0) or None,
                          n_alloc)
    k, v = _halves(body, shape, dtype)
    return k, v, seq_len, real_nb


def join_chunks(chunks: Sequence[tuple[Mapping[str, str], bytes]]
                ) -> tuple[bytes, dict[str, str]] | None:
    """Chunk responses (headers, body) in order -> the body and geometry
    headers of the whole export, as one :func:`encode` of all its blocks; None
    when no chunk carried bytes (an exporter whose pages are not on its
    host)."""
    ks, vs = [], []
    for headers, body in chunks:
        if not headers.get(H_CHUNK_SHAPE):
            continue
        shape = tuple(int(d) for d in json.loads(headers[H_CHUNK_SHAPE]))
        k, v = _halves(body, shape, jnp.dtype(headers[H_DTYPE]))
        ks.append(k)
        vs.append(v)
    if not ks:
        return None
    return encode(np.concatenate(ks, axis=1), np.concatenate(vs, axis=1))
