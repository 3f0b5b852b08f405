"""The plain reference of a resharding restore: the whole-tensor restore.

Each source shard file of a leaf is read whole through ``view.read_file``
(no offset runs, no pipeline), the whole leaf is assembled in numpy from
the manifest's grid indices, and it is sliced by the target sharding's
``addressable_devices_indices_map``. It imports nothing of the program
under test; the view it reads through is the caller's.

A restored leaf is held against it by digests taken right after the
restore (``placement``), since the job trains on, and donates, the arrays
it restored.
"""

from __future__ import annotations

import io
from typing import Dict, List, Tuple

import numpy as np

from benchkit.refs import leaf_digests

Index = Tuple[Tuple[int, int], ...]


def _dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:  # bfloat16 and the float8s live in ml_dtypes
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def whole_leaf(view, rec: Dict) -> np.ndarray:
    """One leaf of a manifest, assembled from its shard files read whole."""
    dtype = _dtype(rec["dtype"])
    out = np.empty(tuple(rec["shape"]), dtype)
    covered = 0
    for s in rec["shards"]:
        arr = np.load(io.BytesIO(view.read_file(s["path"])))
        if arr.dtype != dtype:  # stored as a same-width integer view
            arr = arr.view(dtype)
        out[tuple(slice(lo, hi) for lo, hi in s["index"])] = arr
        covered += arr.size
    if covered != out.size:
        raise ValueError(f"the shards of {rec['shards'][0]['path']} cover "
                         f"{covered} of {out.size} elements")
    return out


def _index(index, shape) -> Index:
    return tuple((sl.indices(d)[0], sl.indices(d)[1])
                 for sl, d in zip(index, shape))


def placement(leaves: List, targets: List) -> List[Dict]:
    """What a restore put where, leaf by leaf: whether the leaf's sharding
    is its target's, the slice each device should hold under the target,
    and the slice and digest of what each device does hold."""
    out = []
    for leaf, target in zip(leaves, targets):
        shape = leaf.shape
        want = {d.id: _index(idx, shape) for d, idx in
                target.addressable_devices_indices_map(shape).items()}
        got = {s.device.id: (_index(s.index, shape),
                             leaf_digests([s.data])[0])
               for s in leaf.addressable_shards}
        out.append({"target": leaf.sharding.is_equivalent_to(target,
                                                             len(shape)),
                    "want": want, "got": got})
    return out


def shards_wrong(full: np.ndarray, placed: Dict) -> int:
    """Device shards of one restored leaf that are not the reference's
    slice for that device, plus one where the leaf's sharding is not its
    target's."""
    wrong = int(not placed["target"])
    for dev, idx in placed["want"].items():
        got = placed["got"].get(dev)
        ref = full[tuple(slice(lo, hi) for lo, hi in idx)]
        wrong += (got is None or got[0] != idx
                  or got[1] != leaf_digests([ref])[0])
    return wrong + len(set(placed["got"]) - set(placed["want"]))
