"""The disagg KV-block wire format: content-addressed manifests and
packed block payloads.

A manifest is the chain-hash list of a prompt's matchable full blocks
(:func:`prompt_manifest` — the same ``chain_hash`` chain every
replica's scheduler computes, so the prefill pool, the decode pool,
and the router all name blocks identically without exchanging tokens).
A payload (:func:`pack_blocks` / :func:`unpack_blocks`) carries the
actual K/V contents of a hash subset as base64 inside the JSON body of
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


def pack_blocks(hashes: Sequence[str], k_np: np.ndarray, v_np: np.ndarray,
                wire_dtype: str = "native") -> Dict:
    """The ``/v1/kv/fetch`` response document for ``hashes``' block
    contents (``k_np``/``v_np`` shaped ``(layers, n, bs, row)``, the
    pools' own layout; the shape travels in the document).
    Returns ``{"hashes", "shape", "dtype", "wire_dtype", "k", "v"}``;
    an empty ``hashes`` packs to ``{"hashes": []}``."""
    if wire_dtype not in WIRE_DTYPES:
        raise ValueError(
            f"HVD_TPU_DISAGG_WIRE_DTYPE={wire_dtype!r}: must be one of "
            f"{'|'.join(WIRE_DTYPES)}")
    hashes = [str(h) for h in hashes]
    if not hashes:
        return {"hashes": []}
    k_b64, wire_name = _encode(np.asarray(k_np), wire_dtype)
    v_b64, _ = _encode(np.asarray(v_np), wire_dtype)
    return {"hashes": hashes,
            "shape": list(np.asarray(k_np).shape),
            "dtype": str(np.asarray(k_np).dtype),
            "wire_dtype": wire_name,
            "k": k_b64, "v": v_b64}


def unpack_blocks(doc: Dict) -> Tuple[List[str], Optional[np.ndarray],
                                      Optional[np.ndarray], int]:
    """Invert :func:`pack_blocks`:
    ``(hashes, k_np, v_np, wire_bytes)``. Arrays come back in the wire
    dtype (the importer's ``scatter_blocks`` casts to the pool dtype);
    ``wire_bytes`` is the payload size actually moved, the
    ``hvd_tpu_disagg_transfer_bytes_total`` increment."""
    hashes = [str(h) for h in doc.get("hashes", [])]
    if not hashes:
        return [], None, None, 0
    shape = doc["shape"]
    wire_name = doc.get("wire_dtype") or doc["dtype"]
    k_np = _decode(doc["k"], wire_name, shape)
    v_np = _decode(doc["v"], wire_name, shape)
    return hashes, k_np, v_np, k_np.nbytes + v_np.nbytes
