"""The disagg KV-block wire format: content-addressed manifests and
packed block payloads.

A manifest is the chain-hash list of a prompt's matchable full blocks
(:func:`prompt_manifest` — the same ``chain_hash`` chain every
replica's scheduler computes, so the prefill pool, the decode pool,
and the router all name blocks identically without exchanging tokens).
A payload (:func:`pack_blocks` / :func:`unpack_blocks`) carries the
actual cache contents of a hash subset (every pool the model's cache
declaration names) as base64 inside the JSON body of
``POST /v1/kv/fetch`` — self-describing (shape + dtypes ride along),
so a fetch can be answered and verified without out-of-band context.

``wire_dtype`` mirrors the PR 7 compression registry's bf16 wire
codec: ``'native'`` ships the pool dtype bit-exactly (the default —
the disagg-vs-colocated bit-parity guarantee requires it whenever the
pools are wider than bf16), ``'bf16'`` halves fp32 transfer bytes by
round-tripping through ``jnp.bfloat16`` (lossless only when the pools
already are bf16).
"""

import base64
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..generation.kv_cache import chain_hash

#: wire dtypes the fetch endpoint accepts
WIRE_DTYPES = ("native", "bf16")


def prompt_manifest(tokens: Sequence[int], block_size: int) -> List[str]:
    """Chain hashes of ``tokens``' matchable full blocks — capped below
    the final token, exactly like the scheduler's admission hashes
    (prefill must keep at least one token to run, because the prefill
    program samples the first generated token)."""
    toks = [int(t) for t in tokens]
    bs = int(block_size)
    n = max(0, (len(toks) - 1) // bs)
    out: List[str] = []
    parent: Optional[str] = None
    for j in range(n):
        parent = chain_hash(parent, toks[j * bs:(j + 1) * bs])
        out.append(parent)
    return out


def _encode(arr: np.ndarray, wire_dtype: str) -> Tuple[str, str]:
    """One pool-slice array -> (base64 payload, wire dtype name)."""
    if wire_dtype == "bf16":
        import jax.numpy as jnp
        arr = np.asarray(arr).astype(jnp.bfloat16)
    raw = np.ascontiguousarray(arr).tobytes()
    return base64.b64encode(raw).decode("ascii"), str(arr.dtype)


def _decode(b64: str, dtype_name: str, shape: Sequence[int]) -> np.ndarray:
    raw = base64.b64decode(b64.encode("ascii"))
    if dtype_name == "bfloat16":
        import jax.numpy as jnp
        dt = jnp.bfloat16
    else:
        dt = np.dtype(dtype_name)
    return np.frombuffer(raw, dtype=dt).reshape(tuple(shape))


def pack_blocks(hashes: Sequence[str], rows,
                wire_dtype: str = "native") -> Dict:
    """The ``/v1/kv/fetch`` response document for ``hashes``' block
    contents. ``rows`` is what
    :func:`~horovod_tpu.serving.generation.kv_cache.gather_blocks`
    returns: one ``(planes, n, bs, row)`` array for each pool of the
    model's cache declaration, in its order (K and V for the GPT-2
    block, one latent row for latent attention); every array's shape
    and dtypes travel in the document, so the codec knows nothing of a
    model. Returns ``{"hashes", "rows": [{"shape", "dtype",
    "wire_dtype", "data"}, ...]}``; an empty ``hashes`` packs to
    ``{"hashes": []}``."""
    if wire_dtype not in WIRE_DTYPES:
        raise ValueError(
            f"HVD_TPU_DISAGG_WIRE_DTYPE={wire_dtype!r}: must be one of "
            f"{'|'.join(WIRE_DTYPES)}")
    hashes = [str(h) for h in hashes]
    if not hashes:
        return {"hashes": []}
    packed = []
    for arr in rows:
        arr = np.asarray(arr)
        data, wire_name = _encode(arr, wire_dtype)
        packed.append({"shape": list(arr.shape), "dtype": str(arr.dtype),
                       "wire_dtype": wire_name, "data": data})
    return {"hashes": hashes, "rows": packed}


def unpack_blocks(doc: Dict) -> Tuple[List[str], Optional[Tuple], int]:
    """Invert :func:`pack_blocks`: ``(hashes, rows, wire_bytes)``.
    Arrays come back in the wire dtype (the importer's
    ``scatter_blocks`` casts to the pool dtype); ``wire_bytes`` is the
    payload size actually moved, the
    ``hvd_tpu_disagg_transfer_bytes_total`` increment."""
    hashes = [str(h) for h in doc.get("hashes", [])]
    if not hashes:
        return [], None, 0
    rows = tuple(
        _decode(r["data"], r.get("wire_dtype") or r["dtype"], r["shape"])
        for r in doc["rows"])
    return hashes, rows, sum(r.nbytes for r in rows)
