"""Program fingerprints + topology metadata for the engine's bucket key.

Same-signature engines of one process share a bucket's executable
(`inference/engine.py`) under a key with two halves:

- the **program fingerprint**: a sha256 over a canonical description of the
  bucket program (model dims, pool dtype, bucket kind/size, aval signature,
  shardings, donation) — everything the compiled artifact depends on and
  nothing it doesn't (weight VALUES are runtime arguments, so two replicas
  of the same model share a fingerprint by construction);
- the **topology meta**: jax version, backend platform, device count, mesh
  axis sizes and the mesh's device ids. An executable compiled for one
  device set must never run another's traffic, so the meta participates in
  the key.
"""
from __future__ import annotations

import hashlib
import json
from typing import Optional

__all__ = [
    "fingerprint_text",
    "topology_meta",
    "topology_key",
    "entry_key",
    "aval_signature",
]


def fingerprint_text(text: str) -> str:
    """sha256 (hex, truncated to 32 chars) of a canonical program text."""
    return hashlib.sha256(text.encode("utf-8", "replace")).hexdigest()[:32]


def topology_meta(mesh=None) -> dict:
    """The environment half of a share key: everything that must match for
    one engine's executable to serve another's traffic."""
    meta = {"jax_version": None, "platform": "unknown", "device_count": 0,
            "mesh_shape": None}
    try:
        import jax

        meta["jax_version"] = jax.__version__
        devs = jax.devices()
        meta["platform"] = devs[0].platform
        meta["device_count"] = len(devs)
    except Exception:
        pass
    if mesh is not None:
        try:
            meta["mesh_shape"] = {str(k): int(v) for k, v in dict(mesh.shape).items()}
        except Exception:
            meta["mesh_shape"] = str(getattr(mesh, "shape", None))
        # the DEVICE SET, not just the shape: two fleet replicas on
        # disjoint same-shape submeshes compile executables pinned to
        # different devices — sharing across them runs replica B's traffic
        # on replica A's devices
        try:
            meta["mesh_devices"] = [int(d.id) for d in mesh.devices.flat]
        except Exception:
            meta["mesh_devices"] = None
    return meta


def topology_key(meta: Optional[dict] = None) -> str:
    """Short stable digest of a topology meta (participates in entry
    keys)."""
    meta = meta if meta is not None else topology_meta()
    return hashlib.sha256(
        json.dumps(meta, sort_keys=True).encode()
    ).hexdigest()[:16]


def entry_key(fingerprint: str, meta: Optional[dict] = None) -> str:
    """Share key: (fingerprint, topology meta, jax version) — the jax
    version rides inside the meta."""
    return f"{fingerprint}-{topology_key(meta)}"


def aval_signature(tree) -> str:
    """Canonical text for a pytree of arrays/ShapeDtypeStructs: the aval
    half of a fingerprint (shape+dtype per leaf, structure included)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    parts = [
        f"{tuple(getattr(l, 'shape', ()))}:{getattr(l, 'dtype', type(l).__name__)}"
        for l in leaves
    ]
    return f"{treedef}|{';'.join(str(p) for p in parts)}"
