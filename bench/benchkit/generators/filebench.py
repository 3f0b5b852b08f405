"""Filebench personalities on a ``bento`` mount: a fileset and a flow of
flowops, run by ``nthreads`` threads in a closed loop.

The configuration gives the fileset (Filebench's ``nfiles``,
``meandirwidth``, gamma-distributed sizes by ``filesize_mean`` and
``filesize_gamma``, ``prealloc`` percent). The traffic gives the flow:
a list of flowops, each one PosixView call through ``Mount.submit``,
except ``fsync``, which PosixView makes as a scalar call.

- ``delete``: unlink a random existing file of the thread's stripe;
- ``create``: create a random absent file of the stripe; it becomes the
  thread's open file;
- ``open``: pick a random existing file as the open file and ``stat``
  it (the lookup an open makes); ``close`` makes no call and is left out;
- ``append``: write a random 1..``meanappendsize`` bytes at the end of
  the open file;
- ``write_whole``: write a just-created (empty) open file whole, its
  size drawn from the fileset's distribution;
- ``stat``: stat a random existing file;
- ``read_whole``: read the open file to its end;
- ``fsync``: fsync the open file.

Each thread owns the files whose index is its own modulo ``nthreads``,
so a plain per-thread model of names, sizes and CRC32s is an exact
reference. Every read in the window is held against it, and after the
window the mount is dropped without an unmount and a cold remount from
the device alone must hold every file the model holds, byte for byte.
"""

from __future__ import annotations

import math
import threading
import time
import traceback
import zlib
from typing import Dict, List

import numpy as np

from benchkit.faults import VolatileWrites
from benchkit.meter import span

POOL_BYTES = 4 << 20  # random content that writes take slices of
SETUP_BATCH = 32      # files created per fsync'd submission in set-up
WARMUP_S = 2.0        # the flow runs this long in set-up


class _Fileset:
    def __init__(self, conf: Dict, rng: np.random.Generator):
        self.n = int(conf["nfiles"])
        width = int(conf["meandirwidth"])
        self.levels = max(0, math.ceil(math.log(self.n) / math.log(width))
                          - 1) if width < self.n else 0
        self.width = width
        self.root = "/" + conf["fileset_name"]
        self.mean = float(conf["filesize_mean"])
        self.shape = float(conf["filesize_gamma"])
        self.exists = rng.random(self.n) < conf["prealloc_percent"] / 100.0
        self.dirs = self._dirs()

    def path(self, i: int) -> str:
        dirs, q = [], i // self.width
        for _ in range(self.levels):
            q, r = divmod(q, self.width)
            dirs.append(f"d{r:02d}")
        return "/".join([self.root, *dirs, f"f{i:07d}"])

    def _dirs(self) -> List[str]:
        out = {self.root}
        for i in range(self.n):
            p = self.path(i).rsplit("/", 1)[0]
            while p not in out:
                out.add(p)
                p = p.rsplit("/", 1)[0]
        return sorted(out, key=lambda p: (p.count("/"), p))

    def size(self, rng: np.random.Generator) -> int:
        return int(round(rng.gamma(self.shape, self.mean / self.shape)))


class _Thread:
    """One Filebench thread: its stripe, its model and its records."""

    def __init__(self, tid: int, files: List[int], exists, rng, pool):
        self.tid = tid
        self.rng = rng
        self.pool = pool
        self.present = {i for i in files if exists[i]}
        self.absent = sorted(set(files) - self.present)
        self.model: Dict[int, tuple] = {}  # index -> (size, crc32)
        self.cur = None
        self.lat: List[float] = []
        self.reads: List[tuple] = []  # (got size, got crc, want size, crc)
        self.ops = 0
        self.failed = 0
        self.errors: List[str] = []

    def data(self, n: int) -> bytes:
        off = int(self.rng.integers(0, len(self.pool) - n + 1))
        return self.pool[off:off + n]

    def pick(self, pool) -> int:
        items = sorted(pool)
        return items[int(self.rng.integers(0, len(items)))]


class Generator:
    def __init__(self, config: Dict, traffic: Dict, *, seed: int, meter,
                 control: bool = False):
        self.config, self.traffic = config, traffic
        self.seed = seed
        self.meter = meter
        self.control = control
        self.nthreads = int(traffic["nthreads"])
        self.flow = [f["op"] for f in traffic["flow"]]
        self.append_max = int(traffic["meanappendsize"])
        self.attempted = 0
        self.failed = 0
        self.mf = None

    # --- set-up ----------------------------------------------------------------
    def draw(self) -> list:
        """Everything the seed decides before the first op: the fileset,
        each thread's stripe and random stream, and the preallocated
        files' contents, as ``(path, bytes)``."""
        ss = np.random.SeedSequence(self.seed)
        rngs = [np.random.default_rng(s) for s in ss.spawn(self.nthreads + 1)]
        main = rngs[-1]
        pool = main.bytes(POOL_BYTES)
        self.fs = _Fileset(self.config, main)
        self.threads = [
            _Thread(t, list(range(t, self.fs.n, self.nthreads)),
                    self.fs.exists, rngs[t], pool)
            for t in range(self.nthreads)]
        items = []
        for th in self.threads:
            for i in sorted(th.present):
                body = th.data(min(self.fs.size(main), POOL_BYTES))
                th.model[i] = (len(body), zlib.crc32(body))
                items.append((self.fs.path(i), body))
        return items

    def setup(self) -> None:
        from repro.fs.mounts import make_mount

        items = self.draw()
        self.mf = make_mount("bento", n_blocks=int(self.config["device_blocks"]))
        self.dev = self.mf.dev
        self.meter.attach(self.mf.services)
        view = self.mf.view
        for d in self.fs.dirs:
            view.mkdir(d)
        for lo in range(0, len(items), SETUP_BATCH):
            view.create_and_write_many(items[lo:lo + SETUP_BATCH], fsync=True)
        # every bucket of the journal's batched hash, then the flow itself
        ks = self.mf.services
        for n in (1, 9, 17, 33):
            ks.checksum_batch([bytes(4096)] * n)
        self._run(WARMUP_S)
        if self.failed:
            raise RuntimeError("the warm-up failed:\n"
                               + next(e for t in self.threads
                                      for e in t.errors))
        for th in self.threads:
            th.lat, th.ops = [], 0
        self.attempted = 0
        self.meter.reset()

    # --- the flow ------------------------------------------------------------------
    def _op(self, th: _Thread, op: str) -> None:
        view, fs = self.mf.view, self.fs
        if op == "delete":
            i = th.pick(th.present)
            t0 = time.perf_counter()
            with span("bench.flowop.delete"):
                view.unlink_many([fs.path(i)])
            th.lat.append(time.perf_counter() - t0)
            th.present.discard(i)
            th.absent.append(i)
            th.model.pop(i)
            th.cur = None
        elif op == "create":
            i = th.absent.pop(int(th.rng.integers(0, len(th.absent))))
            t0 = time.perf_counter()
            with span("bench.flowop.create"):
                view.create_many([fs.path(i)])
            th.lat.append(time.perf_counter() - t0)
            th.present.add(i)
            th.model[i] = (0, 0)
            th.cur = i
        elif op in ("open", "stat"):
            i = th.pick(th.present)
            t0 = time.perf_counter()
            with span("bench.flowop." + op):
                view.stat_many([fs.path(i)])
            th.lat.append(time.perf_counter() - t0)
            if op == "open":
                th.cur = i
        elif op in ("append", "write_whole"):
            i = th.cur
            size, crc = th.model[i]
            if op == "append":
                body = th.data(int(th.rng.integers(1, self.append_max + 1)))
                off, crc = size, zlib.crc32(body, crc)
            else:
                if size:
                    raise ValueError("write_whole writes an empty file")
                body = th.data(min(fs.size(th.rng), POOL_BYTES))
                off, crc = 0, zlib.crc32(body)
            t0 = time.perf_counter()
            with span("bench.flowop." + op):
                view.write_many([(fs.path(i), off, body)], create=False)
            th.lat.append(time.perf_counter() - t0)
            th.model[i] = (max(size, off + len(body)), crc)
        elif op == "read_whole":
            i = th.cur
            t0 = time.perf_counter()
            with span("bench.flowop.read_whole"):
                got = view.read_many([fs.path(i)])[0]
            th.lat.append(time.perf_counter() - t0)
            th.reads.append((len(got), zlib.crc32(got)) + th.model[i])
        elif op == "fsync":
            t0 = time.perf_counter()
            with span("bench.flowop.fsync"):
                view.fsync(fs.path(th.cur))
            th.lat.append(time.perf_counter() - t0)
        else:
            raise ValueError(f"unknown flowop {op!r}")
        th.ops += 1

    def _loop(self, th: _Thread, start: threading.Barrier, until: list):
        start.wait()
        try:
            while time.perf_counter() < until[0]:
                for op in self.flow:
                    self._op(th, op)
        except Exception:  # noqa: BLE001 — a failed op is a result
            th.failed += 1
            th.errors.append(traceback.format_exc())

    def _run(self, seconds: float) -> float:
        """All threads run whole flows until ``seconds`` have passed;
        returns the seconds from the start until the last one stopped."""
        start = threading.Barrier(self.nthreads + 1)
        until = [math.inf]
        workers = [threading.Thread(target=self._loop, args=(th, start, until),
                                    name=f"filebench-{th.tid}")
                   for th in self.threads]
        for w in workers:
            w.start()
        t0 = time.perf_counter()
        until[0] = t0 + seconds
        start.wait()
        for w in workers:
            w.join()
        self.failed = sum(th.failed for th in self.threads)
        self.attempted = sum(th.ops + th.failed for th in self.threads)
        return time.perf_counter() - t0

    # --- the window ---------------------------------------------------------------
    def window(self, seconds: float) -> None:
        self.volatile = VolatileWrites(self.dev) if self.control else None
        if self.volatile is not None:
            self.volatile.arm()
        journal = self.mf.mount.module.journal
        cache = self.mf.services._cache
        c0 = (journal.commits, self.mf.mount.gate.crossings, cache.hits,
              cache.misses)
        self.meter.phase = "fs"
        self.window_s = self._run(seconds)
        self.meter.phase = None
        c1 = (journal.commits, self.mf.mount.gate.crossings, cache.hits,
              cache.misses)
        self.counters = dict(zip(
            ("journal_commits", "gate_crossings", "cache_hits",
             "cache_misses"), (b - a for a, b in zip(c0, c1))))
        self.counters["ops"] = sum(th.ops for th in self.threads)

    def release(self) -> None:
        """Kill: drop the mount without an unmount. The control's device
        loses every write the window made."""
        if self.volatile is not None:
            self.volatile.lose()
        self.mf = None

    # --- results -------------------------------------------------------------------
    def latencies(self) -> np.ndarray:
        return np.array([x for th in self.threads for x in th.lat])

    def end_to_end(self) -> Dict[str, float]:
        lat = self.latencies()
        if not lat.size:
            return {}
        return {"ops_per_s": lat.size / self.window_s,
                "op_p99_ms": float(np.percentile(lat, 99)) * 1e3}

    def record(self) -> Dict:
        return {"hash": dict(self.meter.totals),
                "counters": {"fs": dict(self.counters)}, "samples": {}}

    def check(self) -> List[tuple]:
        from repro.fs.mounts import remount

        reads_wrong = sum(r[:2] != r[2:] for th in self.threads
                          for r in th.reads)
        view = remount(self.dev).view
        model = {self.fs.path(i): m for th in self.threads
                 for i, m in th.model.items()}
        dirs, have = set(self.fs.dirs), set()
        for d in dirs:
            try:
                names = view.listdir(d)
            except Exception:  # noqa: BLE001 — a lost dir: its files count
                continue
            have |= {f"{d}/{n}" for n in names if n not in (".", "..")}
        have -= dirs
        names_wrong = len(have ^ set(model))
        files_wrong = 0
        paths = sorted(set(model) & have)
        for lo in range(0, len(paths), 64):
            chunk = paths[lo:lo + 64]
            got = view.read_many(chunk, strict=False)
            for p, g in zip(chunk, got):
                files_wrong += (isinstance(g, Exception)
                                or (len(g), zlib.crc32(g)) != model[p])
        return [("ops_failed", self.failed, 0),
                ("reads_wrong", reads_wrong, 0),
                ("names_wrong_after_remount", names_wrong, 0),
                ("files_wrong_after_remount", files_wrong, 0)]

    def diagnostics(self) -> List[str]:
        return [e for th in self.threads for e in th.errors]
