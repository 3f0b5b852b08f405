"""Topology-elastic checkpoint benchmark: reshard-on-restore through the
batched FS path, plus the multi-tenant provisioning stories on top of it.

Three self-asserting phases (the acceptance bar, not a human eyeballing
numbers):

* **elastic** — a seeded model saved shard-per-file on mesh A (2x2) is
  restored onto the SAME, a HALVED (1x2) and a DOUBLED (4x2) mesh:
  every leaf must come back byte-identical to the whole-tensor reference
  with the target topology's sharding, and every leaf whose target shards
  are proper subsets of the tensor must assemble with peak materialized
  bytes strictly BELOW full-tensor size (the streamed ``read_many``
  reshard path — a restore that gathers full leaves fails here).
* **overlap** — the same elastic reshard through the FUSE daemon (a real
  address-space crossing per fetch), restored serial (pipeline depth 0:
  the legacy verify-then-fill two-pass) vs overlapped (depth 2: folded
  verification + prefetch-while-assemble). Best-of-N wall clock; the
  overlapped engine must beat serial >= 1.3x on the halved+doubled
  reshard cells combined, per-leaf metered peak must stay strictly below
  full-tensor bytes for properly sharded targets AND within depth x the
  serial engine's peak for every streamed leaf.
* **tenants** — N overlay tenants over ONE golden base image carrying the
  checkpoint each restore it through their CoW mount: byte-identical per
  tenant, the shared image untouched, and the blocks materialized per
  tenant a bounded fraction of the image (restore reads ride the lazy
  batched fetch path).
* **dedup** — N identical checkpoints saved to distinct roots of a
  dedup mount must physically cost ~one checkpoint: the content-addressed
  blockstore absorbs the clones (logical - physical = saved blocks).

CLI:  PYTHONPATH=src python -m benchmarks.fs_reshard [--quick]
      [--tenants 8] [--skip-elastic]
"""

from __future__ import annotations

import os
import time
from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import checkpoint as ckpt
from repro.distributed.resharding import ShardGrid
from repro.fs.mounts import build_base_image, make_mount, overlay_tenant
from repro.launch.mesh import make_elastic_mesh

SPECS = {
    "w1": P("data", "model"),
    "w2": P("model", "data"),
    "e": P("model", None),
    "b": P("data"),
    "r": P(),
    "s": P(),
}


def _host_tree(scale: int) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(7)
    return {
        "w1": rng.normal(size=(64 * scale, 32 * scale)).astype(np.float32),
        "w2": rng.normal(size=(32 * scale, 16 * scale)).astype(np.float32),
        "e": rng.normal(size=(16 * scale, 8 * scale)).astype(np.float32),
        "b": rng.normal(size=(64 * scale,)).astype(np.float32),
        "r": rng.normal(size=(8, 8)).astype(np.float32),
        "s": np.float32(1.25),
    }


def _host_mesh(data: int, model: int):
    """A mesh over the host's CPU devices, whatever accelerator is there."""
    cpus = jax.devices("cpu")
    if len(cpus) < 8:
        raise RuntimeError("elastic phases need 8 host CPU devices "
                           "(XLA_FLAGS was set too late)")
    return make_elastic_mesh(data, model, devices=cpus)


def run_elastic(scale: int = 4) -> Dict:
    """Save on (2,2), restore onto same/halved/doubled — asserted."""
    host = _host_tree(scale)
    mesh_a = _host_mesh(2, 2)
    sh_a = {k: NamedSharding(mesh_a, SPECS[k]) for k in host}
    tree = {k: jax.device_put(jnp.asarray(v), sh_a[k])
            for k, v in host.items()}
    total_bytes = sum(v.nbytes for v in host.values())

    mf = make_mount("bento", n_blocks=65536)
    cks = mf.services.checksum
    t0 = time.perf_counter()
    man = ckpt.save(mf.view, "/ck/step_1", tree, step=1, checksum=cks,
                    shardings=sh_a)
    save_s = time.perf_counter() - t0
    assert man["version"] == 2
    n_shard_files = sum(len(r["shards"]) for r in man["leaves"])
    assert n_shard_files > len(man["leaves"]), "nothing actually sharded"

    like = {k: jnp.zeros(v.shape, v.dtype) for k, v in host.items()}
    topos = {"same": (2, 2), "halved": (1, 2), "doubled": (4, 2)}
    out = {"bench": "fs_reshard", "phase": "elastic",
           "leaf_bytes_total": total_bytes, "shard_files": n_shard_files,
           "save_s": save_s, "restores": {}}
    for name, (d, m) in topos.items():
        mesh_b = _host_mesh(d, m)
        sh_b = {k: NamedSharding(mesh_b, SPECS[k]) for k in host}
        stats: Dict = {}
        t0 = time.perf_counter()
        back, _ = ckpt.load(mf.view, "/ck/step_1", like, checksum=cks,
                            sharding_tree=sh_b, stats=stats)
        restore_s = time.perf_counter() - t0
        for k, ref in host.items():  # byte-identical + right topology
            got = np.asarray(jax.device_get(back[k]))
            assert got.dtype == ref.dtype and got.shape == ref.shape
            assert (got == ref).all(), f"{name}: leaf {k} corrupted"
            assert back[k].sharding.devices_indices_map(ref.shape) == \
                sh_b[k].devices_indices_map(ref.shape), (name, k)
        # bounded peak: every properly-sharded streamed leaf assembles
        # strictly below full-tensor bytes; replicated targets (or axes
        # collapsed to 1 on the halved mesh) legitimately materialize
        # the whole leaf and are exempt by construction
        strict = [s for s in stats["leaves"]
                  if s["streamed"] and
                  s["max_target_bytes"] < s["full_bytes"]]
        for s in strict:
            assert s["peak_bytes"] < s["full_bytes"], (
                f"{name}: leaf {s['leaf']} peaked at {s['peak_bytes']} "
                f">= full {s['full_bytes']} — restore gathered the tensor")
        assert len(strict) >= 2, (name, stats["leaves"])
        worst = max(s["peak_bytes"] / s["full_bytes"] for s in strict)
        out["restores"][name] = {
            "mesh": [d, m], "restore_s": restore_s,
            "streamed_leaves": sum(1 for s in stats["leaves"]
                                   if s["streamed"]),
            "strict_leaves": len(strict), "worst_peak_fraction": worst,
        }
    mf.close()
    return out


def run_overlap(scale: int = 32, depth: int = 2, reps: int = 4,
                min_speedup: float = 1.3) -> Dict:
    """Overlapped (prefetch-while-assemble) vs serial restore through the
    FUSE daemon — the store where fetch latency is a real address-space
    crossing, i.e. the regime the restore pipeline exists for."""
    import zlib

    def cks(raw):  # the userspace binding's checksum (services daemon-side)
        return zlib.crc32(bytes(raw)) & 0xFFFFFFFF

    host = _host_tree(scale)
    like = {k: jnp.zeros(v.shape, v.dtype) for k, v in host.items()}

    mf = make_mount("fuse", n_blocks=65536)
    try:
        return _run_overlap_cells(mf, cks, host, like, depth, reps,
                                  min_speedup)
    finally:
        mf.close()  # a failed assert must not leak the daemon


def _run_overlap_cells(mf, cks, host, like, depth, reps,
                       min_speedup) -> Dict:
    out = {"bench": "fs_reshard", "phase": "overlap", "depth": depth,
           "leaf_bytes_total": sum(v.nbytes for v in host.values()),
           "cells": {}}
    sh_a = {k: NamedSharding(_host_mesh(2, 2), SPECS[k])
            for k in host}
    tree = {k: jax.device_put(jnp.asarray(v), sh_a[k])
            for k, v in host.items()}
    ckpt.save(mf.view, "/ck/step_1", tree, step=1, checksum=cks,
              shardings=sh_a)
    serial_total = piped_total = 0.0
    for name, (d, m) in (("halved", (1, 2)), ("doubled", (4, 2))):
        mesh_b = _host_mesh(d, m)
        sh_b = {k: NamedSharding(mesh_b, SPECS[k]) for k in host}
        # untimed warm-up: first restore onto a fresh target mesh pays
        # one-off device_put/layout costs that belong to neither engine
        ckpt.load(mf.view, "/ck/step_1", like, checksum=cks,
                  sharding_tree=sh_b, pipeline_depth=depth)
        best = {}
        for dep in (0, depth):
            best[dep] = (1e9, None)
            for _ in range(reps):
                stats: Dict = {}
                t0 = time.perf_counter()
                back, _ = ckpt.load(mf.view, "/ck/step_1", like,
                                    checksum=cks, sharding_tree=sh_b,
                                    stats=stats, pipeline_depth=dep)
                dt = time.perf_counter() - t0
                if dt < best[dep][0]:
                    best[dep] = (dt, stats)
            for k, ref in host.items():  # both engines: byte-identical
                assert (np.asarray(jax.device_get(back[k])) == ref).all(), \
                    f"overlap/{name} depth {dep}: leaf {k} corrupted"
        serial_s, serial_stats = best[0]
        piped_s, piped_stats = best[depth]
        serial_total += serial_s
        piped_total += piped_s
        # peak discipline: strictly sub-full for properly sharded
        # targets, and within depth x the serial engine's metered peak
        serial_peak = {s["leaf"]: s["peak_bytes"]
                       for s in serial_stats["leaves"]}
        strict = 0
        for s in piped_stats["leaves"]:
            if not s["streamed"]:
                continue
            assert s["peak_bytes"] <= depth * serial_peak[s["leaf"]], (
                f"overlap/{name}: leaf {s['leaf']} peak {s['peak_bytes']} "
                f"exceeds depth x serial peak "
                f"{depth * serial_peak[s['leaf']]}")
            if s["max_target_bytes"] < s["full_bytes"]:
                assert s["peak_bytes"] < s["full_bytes"], (
                    f"overlap/{name}: leaf {s['leaf']} gathered the "
                    f"tensor ({s['peak_bytes']} >= {s['full_bytes']})")
                strict += 1
        assert strict >= 2, (name, piped_stats["leaves"])
        out["cells"][name] = {
            "mesh": [d, m], "serial_s": serial_s, "pipelined_s": piped_s,
            "speedup": serial_s / piped_s,
            "overlap_ratio": piped_stats["pipeline"]["overlap_ratio"],
        }
    out["speedup_combined"] = serial_total / piped_total
    assert out["speedup_combined"] >= min_speedup, (
        f"overlapped restore only {out['speedup_combined']:.2f}x serial "
        f"across halved+doubled cells (bar: {min_speedup}x) — the "
        f"pipeline is not hiding fetch latency")
    return out


def _virtual_ckpt_save(view, root: str, host: Dict[str, np.ndarray]):
    """Deviceless v2 save (virtual 2x2 grid on the biggest leaf) — the
    tenant/dedup phases shard without touching jax device state."""
    grids = {k: (ShardGrid.from_spec(v.shape, ("d", "m"),
                                     {"d": 2, "m": 2})
                 if len(v.shape) == 2 and min(v.shape) >= 2 else None)
             for k, v in host.items()}
    return ckpt.save(view, root, host, step=1, shardings=grids)


def run_tenants(n_tenants: int = 8, scale: int = 2, *,
                materialize_ceiling: float = 0.25) -> Dict:
    """N tenants restore the SAME checkpoint from one shared base image
    through CoW overlay mounts — the fleet-redeploy story."""
    host = _host_tree(scale)

    def populate(view):
        _virtual_ckpt_save(view, "/ckpt/step_1", host)

    image = build_base_image("xv6", n_blocks=8192, populate=populate)
    image_bytes0 = image._data.tobytes()
    t0 = time.perf_counter()
    tenants = [overlay_tenant(image, "xv6") for _ in range(n_tenants)]
    provision_s = time.perf_counter() - t0
    like = {k: np.zeros(v.shape, v.dtype) for k, v in host.items()}
    t0 = time.perf_counter()
    fetched = []
    for t, mf in enumerate(tenants):
        assert ckpt.latest_step(mf.view, "/ckpt") == 1
        back, man = ckpt.load(mf.view, "/ckpt/step_1", like)
        assert man["version"] == 2
        for k, ref in host.items():
            got = np.asarray(jax.device_get(back[k]))
            assert (got == ref).all(), f"tenant {t}: leaf {k} corrupted"
        mf.view.write_file("/private", b"tenant %d" % t)  # isolation probe
        lazy = mf.mount.module.opts.base_dev
        fetched.append(lazy.provider_blocks_fetched)
    restore_s = time.perf_counter() - t0
    assert tenants[0].view.read_file("/private") == b"tenant 0", \
        "tenant writes leaked across mounts"
    assert image._data.tobytes() == image_bytes0, \
        "a tenant restore wrote to the shared base image"
    frac = max(fetched) / image.n_blocks
    assert frac <= materialize_ceiling, (
        f"restore materialized {max(fetched)} of {image.n_blocks} base "
        f"blocks ({frac:.0%}) — the lazy fetch path regressed")
    for mf in tenants:
        mf.close()
    return {"bench": "fs_reshard", "phase": "tenants",
            "tenants": n_tenants, "provision_s": provision_s,
            "restore_s": restore_s,
            "restore_ms_per_tenant": 1e3 * restore_s / n_tenants,
            "materialized_fraction": frac}


def run_dedup(n_copies: int = 6, scale: int = 2, *,
              marginal_ceiling: float = 0.30) -> Dict:
    """N identical checkpoints on a dedup mount physically cost ~one."""
    host = _host_tree(scale)
    mf = make_mount("dedup-bento", n_blocks=32768)
    free0 = mf.view.statfs()["free_blocks_est"]
    _virtual_ckpt_save(mf.view, "/t0/ckpt", host)
    first_cost = free0 - mf.view.statfs()["free_blocks_est"]
    for t in range(1, n_copies):
        _virtual_ckpt_save(mf.view, f"/t{t}/ckpt", host)
    st = mf.view.statfs()
    total_cost = free0 - st["free_blocks_est"]
    marginal = (total_cost - first_cost) / max(1, n_copies - 1)
    saved = st["free_blocks_logical_est"] - st["free_blocks_est"]
    assert marginal <= marginal_ceiling * first_cost, (
        f"clone checkpoints cost {marginal:.1f} blocks each vs "
        f"{first_cost} for the first — dedup is not absorbing them")
    assert saved >= (n_copies - 1) * first_cost * 0.5, (
        f"only {saved} blocks saved across {n_copies} identical "
        f"checkpoints of {first_cost} blocks")
    like = {k: np.zeros(v.shape, v.dtype) for k, v in host.items()}
    back, _ = ckpt.load(mf.view, f"/t{n_copies - 1}/ckpt", like)
    for k, ref in host.items():
        assert (np.asarray(jax.device_get(back[k])) == ref).all(), \
            f"dedup'd checkpoint corrupted leaf {k}"
    mf.close()
    return {"bench": "fs_reshard", "phase": "dedup", "copies": n_copies,
            "first_cost_blocks": first_cost,
            "marginal_blocks_per_copy": marginal, "saved_blocks": saved}


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small tensors, fewer tenants (CI smoke; same "
                         "asserted bars)")
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--skip-elastic", action="store_true",
                    help="skip the 8-device elastic phase (jax already "
                         "initialized with fewer devices)")
    args = ap.parse_args()
    # 8 host CPU devices for the elastic phases: set before jax starts
    # its backends, which it does at the first device query
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    scale = 2 if args.quick else 4
    n_tenants = 4 if args.quick else args.tenants

    if not args.skip_elastic:
        r = run_elastic(scale=scale)
        print(f"fs_reshard elastic: {r['leaf_bytes_total']} leaf bytes as "
              f"{r['shard_files']} shard files, save {1e3 * r['save_s']:.1f} ms")
        for name, rr in r["restores"].items():
            print(f"  restore {name:8s} mesh {tuple(rr['mesh'])}: "
                  f"{1e3 * rr['restore_s']:7.1f} ms, "
                  f"{rr['streamed_leaves']} streamed leaves, worst peak "
                  f"{rr['worst_peak_fraction']:.2f}x of full (< 1.0) — OK")
        r = run_overlap(scale=16 if args.quick else 32)
        for name, rr in r["cells"].items():
            print(f"fs_reshard overlap {name:8s}: serial "
                  f"{1e3 * rr['serial_s']:7.1f} ms -> depth-{r['depth']} "
                  f"{1e3 * rr['pipelined_s']:7.1f} ms "
                  f"({rr['speedup']:.2f}x)")
        print(f"fs_reshard overlap: combined {r['speedup_combined']:.2f}x "
              f"serial across halved+doubled cells (>= 1.3x) — OK")
    r = run_tenants(n_tenants, scale=2 if args.quick else 3)
    print(f"fs_reshard tenants: {r['tenants']} overlay tenants restored one "
          f"shared checkpoint ({r['restore_ms_per_tenant']:.1f} ms/tenant, "
          f"materialized {r['materialized_fraction']:.1%} of the base "
          f"image) — OK")
    r = run_dedup(4 if args.quick else 6, scale=2 if args.quick else 3)
    print(f"fs_reshard dedup: {r['copies']} identical checkpoints, first "
          f"{r['first_cost_blocks']} blocks, marginal "
          f"{r['marginal_blocks_per_copy']:.1f} blocks/copy, "
          f"{r['saved_blocks']} blocks deduplicated — OK")


if __name__ == "__main__":
    main()
