"""Entry ``route``: host-fed replica routing through ``route_batch``.

A storage front end hands the router batches of keys and waits for each
key's chosen holder.  The timed path is ``RequestStreamDriver.route_batch``
(ASURA R-replica placement on the device, power-of-two-choices selection
against the on-device served counters, counters updated in the same jit),
with every batch's routes copied back to the host.

Arrivals (the traffic file's ``arrivals``):

* ``closed``  -- one client: the next batch of ``batch`` keys is sent when
  the previous batch's routes are on the host;
* ``poisson`` -- an open loop: requests fall due at ``rate_per_s`` from a
  seeded Poisson stream, and the batcher sends the largest power of two of
  the requests that are due, from ``min_batch`` up to ``batch``.  Each
  request's latency runs from its due time until its route is on the host.
  Requests due in the window are all served, after its close if need be.
  ``route_batch`` compiles once per batch length, so the batcher sends
  powers of two only, and at least ``min_batch`` requests: that keeps the
  shapes to warm to a few (the wait for ``min_batch`` arrivals is well under
  a millisecond at the cell's rate).

The check, once the window has closed: the counters' growth equals the
routes received, node by node; every request due got a route; and for a
seeded sample of the served requests the chosen node lies in the
reference's replica set of the key.
"""

from __future__ import annotations

import time

import numpy as np

import generate


def pow2_floor(n: int) -> int:
    return 1 << (int(n).bit_length() - 1)


class RouteProgram:
    """The timed path: the program's serving driver on one engine."""

    def __init__(self, capacities, config: dict, seed: int, backend: str, max_batch: int):
        from repro.core import PlacementEngine, make_cluster
        from repro.serve import RequestStreamDriver

        self.cluster = make_cluster(capacities)
        self.engine = PlacementEngine(self.cluster, backend=backend)
        # route_batch serves external keys; the stream driver's own generated
        # stream is never drawn, so it gets the smallest law there is.
        self.driver = RequestStreamDriver(
            self.engine, batch=max_batch, n_keys=1, law="uniform",
            n_replicas=int(config["replicas"]), policy=config["selection"],
            seed=seed % 2**31,
        )

    def route(self, keys: np.ndarray) -> np.ndarray:
        return np.asarray(self.driver.route_batch(keys))

    def served(self) -> np.ndarray:
        return np.asarray(self.driver.counts)

    def release(self) -> None:
        self.driver = self.engine = self.cluster = None


class Cell:
    def __init__(self, *, config, traffic, seed, seconds, chips, rec, reference, system,
                 backend, log):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.seconds = float(seconds)
        self.rec, self.ref, self.log = rec, reference, log
        self.system_factory, self.backend = system, backend
        self.attempted = self.failed = 0

    # -- set-up ----------------------------------------------------------------

    def setup(self) -> None:
        cfg, tr, rec = self.config, self.traffic, self.rec
        with rec.span("setup.generate"):
            self.capacities = generate.capacities(cfg)
            self.n_bins = int(cfg["nodes"])
            self.table = self.ref.SegmentTable(self.capacities)
            self.pool = generate.key_pool(cfg, tr, self.seed)
            self.flat = np.concatenate([self.pool.ravel(), self.pool[0]])  # wrap-free slices
            self.max_batch = int(tr["batch"])
            if tr["arrivals"] == "closed":
                sizes = [self.max_batch]
            else:
                self.min_batch = int(tr["min_batch"])
                sizes = [1 << b for b in range(self.min_batch.bit_length() - 1,
                                               self.max_batch.bit_length())]
                self.due = generate.poisson_arrivals(float(tr["rate_per_s"]), self.seconds,
                                                     self.seed, self.min_batch)
        with rec.span("setup.system"):
            if self.system_factory is None:
                self.system = RouteProgram(self.capacities, cfg, self.seed, self.backend,
                                           self.max_batch)
            else:
                self.system = self.system_factory(self)
        with rec.span("setup.warm"):
            for b in sizes:  # every shape the window sends, twice: compile, then run
                for _ in range(2):
                    self.system.route(self.pool[0, :b])

    # -- the window ------------------------------------------------------------

    def window(self, seconds: float) -> None:
        self.counts0 = self.system.served()
        if self.traffic["arrivals"] == "closed":
            self._closed(seconds)
        else:
            self._open(seconds)
        self.counts1 = self.system.served()

    def _closed(self, seconds: float) -> None:
        rec, sys_, pool = self.rec, self.system, self.pool
        n_pool = pool.shape[0]
        self.routes: list[np.ndarray] = []
        t0 = time.perf_counter()
        i = 0
        while True:
            with rec.span("route.batch"):
                self.routes.append(sys_.route(pool[i % n_pool]))
            i += 1
            t = time.perf_counter()
            if t - t0 >= seconds:
                break
        self.elapsed = t - t0
        self.batches = i
        self.attempted = i * self.max_batch
        rec.facts.update(batches=i, batch=self.max_batch, n_bins=self.n_bins,
                         table_len=self._table_len())

    def _open(self, seconds: float) -> None:
        rec, sys_, flat, due = self.rec, self.system, self.flat, self.due
        n_pool = self.pool.size
        n = due.shape[0]
        self.routes = []
        self.batch_log: list[tuple[int, int, float, float]] = []  # start, size, dispatch, done
        t0 = time.perf_counter()
        i = 0
        while i < n:
            now = time.perf_counter() - t0
            ready = int(np.searchsorted(due, now, side="right"))
            if ready - i < self.min_batch:
                time.sleep(max(0.0, min(due[i + self.min_batch - 1] - now, 0.001)))
                continue
            b = min(self.max_batch, pow2_floor(ready - i))
            start = i % n_pool
            with rec.span("route.batch"):
                chosen = sys_.route(flat[start:start + b])
            self.routes.append(chosen)
            self.batch_log.append((i, b, now, time.perf_counter() - t0))
            i += b
        self.elapsed = time.perf_counter() - t0
        self.attempted = n
        log = np.asarray(self.batch_log)
        sizes = log[:, 1].astype(np.int64)
        dispatch_at, served_at = np.repeat(log[:, 2], sizes), np.repeat(log[:, 3], sizes)
        self.latency = served_at - due
        wait = dispatch_at - due
        late = log[:, 2] - due[(log[:, 0] + log[:, 1] - 1).astype(np.int64)]
        self.log(f"generator: {len(late)} batches; the newest request of a batch waited "
                 f"{1e3 * late.mean():.4f} ms on average, {1e3 * late.max():.4f} ms at most, "
                 f"before its batch was sent; the last request due at {due[-1]:.4f} s was "
                 f"served at {served_at[-1]:.4f} s")
        rec.facts.update(
            batches=len(self.batch_log), n_bins=self.n_bins, table_len=self._table_len(),
            batch_wait_ms=1e3 * float(wait.mean()),
            service_ms=1e3 * float(np.mean(log[:, 3] - log[:, 2])),
        )

    def _table_len(self) -> int:
        return -(-len(self.table.len32) // 128) * 128  # the program lane-pads tables to 128

    # -- results -----------------------------------------------------------------

    def _served_counts(self) -> np.ndarray:
        """Routes received per node; a route to no node counts nowhere."""
        counts = np.zeros(self.n_bins, dtype=np.int64)
        for r in self.routes:
            ok = (r >= 0) & (r < self.n_bins)
            counts += np.bincount(r[ok], minlength=self.n_bins)
        return counts

    def end_to_end(self) -> dict:
        counts = self._served_counts()
        share = self.capacities / self.capacities.sum()
        out = {"load_skew": float(np.max(counts / (counts.sum() * share)))}
        if self.traffic["arrivals"] == "closed":
            out["routed_per_s"] = self.attempted / self.elapsed
        else:
            out["route_p95_ms"] = 1e3 * float(np.percentile(self.latency, 95))
        self.log(f"window: {self.attempted} requests in {self.elapsed:.6f} s; "
                 f"{len(self.routes)} batches; load skew {out['load_skew']:.6f}")
        return out

    def release(self) -> None:
        self.system.release()
        self.system = None

    def check(self) -> dict:
        """Numbers compared, each ``(value, limit)``."""
        served = self._served_counts()
        counter_gap = int(np.abs((self.counts1 - self.counts0).astype(np.int64) - served).sum())
        routed = int(served.sum())
        unrouted = self.attempted - routed
        keys, chosen = self._sampled(int(self.traffic["check_sample"]))
        uniq, inv = np.unique(keys, return_inverse=True)
        sets = self.ref.place_replicas(uniq, self.table, int(self.config["replicas"]))[inv]
        outside = int((~(sets == chosen[:, None]).any(axis=1)).sum())
        self.log(f"check: {len(keys)} sampled requests ({len(uniq)} distinct keys) against "
                 f"the reference replica sets; {routed} routes against the counters")
        return {
            "outside_replica_set": (outside, 0),
            "counter_gap": (counter_gap, 0),
            "unrouted": (unrouted, 0),
        }

    def _sampled(self, k: int):
        """Keys and chosen nodes of a seeded sample of the routed requests."""
        sizes = np.asarray([len(r) for r in self.routes], dtype=np.int64)
        starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        pos = generate.sample(int(sizes.sum()), k, self.seed, "check")
        bi = np.searchsorted(starts, pos, side="right") - 1
        lane = pos - starts[bi]
        chosen = np.asarray([self.routes[b][l] for b, l in zip(bi, lane)], dtype=np.int64)
        if self.traffic["arrivals"] == "closed":
            keys = self.pool[bi % self.pool.shape[0], lane]
        else:
            first = np.asarray([self.batch_log[b][0] for b in bi], dtype=np.int64)
            keys = self.flat[(first % self.pool.size) + lane]
        return keys, chosen
