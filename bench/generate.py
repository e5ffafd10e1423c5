"""The one traffic generator: turns a configuration, a traffic file and a
seed into the inputs of a run.

Everything a run feeds the system is drawn from ``--seed`` through
independent named streams, so the same seed gives the same keys, arrivals,
tracked ids and events, and a new stream never shifts an old one.  Every
seed draws the same multiset of sizes (event capacities) in another order,
so seeds change which key or object the work falls on, not how much work a
run does.
"""

from __future__ import annotations

import zlib

import numpy as np

from ycsb import ScrambledZipfian


def rng_for(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), zlib.crc32(stream.encode())])


def capacities(config: dict) -> np.ndarray:
    """Node capacities spread evenly over ``capacity_range`` in a shuffled
    order (nodes join in index order).  The cluster is part of the
    configuration, the same for every seed: with the layout drawn from the
    seed, the depth of the placement ladder for the hottest keys changed
    with it, and seeds changed a run's work by 4% (two runs of one seed
    agreed within 1%)."""
    n = int(config["nodes"])
    lo, hi = config["capacity_range"]
    order = rng_for(0, "capacities").permutation(n)
    return lo + (hi - lo) * (order + 0.5) / n


def key_pool(config: dict, traffic: dict, seed: int) -> np.ndarray:
    """``pool_batches`` x ``batch`` YCSB keys, cycled through by the window."""
    law = config["requestdistribution"]
    if law != "zipfian":
        raise ValueError(f"unknown requestdistribution {law!r}")
    gen = ScrambledZipfian(config["recordcount"], config["zipfian_constant"])
    n = int(traffic["pool_batches"]) * int(traffic["batch"])
    return gen.draw(rng_for(seed, "keys"), n).reshape(int(traffic["pool_batches"]), -1)


def poisson_arrivals(rate_per_s: float, seconds: float, seed: int, multiple: int) -> np.ndarray:
    """Due times (s from the window's start) of a Poisson stream, cut to the
    largest multiple of ``multiple`` requests due inside ``seconds``."""
    rng = rng_for(seed, "arrivals")
    n = int(rate_per_s * seconds * 1.1) + 1024
    due = np.cumsum(rng.exponential(1.0 / rate_per_s, n))
    while due[-1] < seconds:  # vanishingly rare: extend the stream
        due = np.concatenate([due, due[-1] + np.cumsum(rng.exponential(1.0 / rate_per_s, n))])
    due = due[due < seconds]
    return due[: len(due) - len(due) % multiple]


def object_ids(config: dict, seed: int) -> np.ndarray:
    """``tracked_objects`` distinct u32 ids: fmix32 (a bijection on u32)
    of consecutive counters from a seeded start."""
    n = int(config["tracked_objects"])
    start = int(rng_for(seed, "objects").integers(0, 2**32))
    h = (np.arange(n, dtype=np.uint64) + np.uint64(start)).astype(np.uint32)
    h ^= h >> np.uint32(16)
    h *= np.uint32(0x85EBCA6B)
    h ^= h >> np.uint32(13)
    h *= np.uint32(0xC2B2AE35)
    h ^= h >> np.uint32(16)
    return h


def event_capacities(traffic: dict, seed: int) -> list[float]:
    """The capacities of the nodes the events add, in a seeded order."""
    caps = list(traffic["add_capacities"])
    order = rng_for(seed, "events").permutation(len(caps))
    return [float(caps[i]) for i in order]


def sample(n: int, k: int, seed: int, stream: str) -> np.ndarray:
    """Sorted positions of a seeded sample of ``min(k, n)`` of ``n``."""
    rng = rng_for(seed, stream)
    return np.sort(rng.choice(n, size=min(k, n), replace=False))
