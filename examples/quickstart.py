"""Quickstart: ASURA in five minutes.

Demonstrates the paper's core API end to end:
  1. build a capacity-weighted cluster (STEP 1),
  2. place data (STEP 2) -- scalar, vectorized, and the Pallas kernel path,
  3. add/remove nodes and observe optimal data movement,
  4. replicate placements and use section-2.D metadata,
  5. route via the paper's comparison baselines through the same engine
     (``Router(algorithm=...)`` -- "asura", "ch", "wrh" or "rs").

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import Cluster, make_cluster
from repro.core.asura import addition_number, remove_numbers
from repro.kernels.ops import asura_place_nodes


def main() -> None:
    # --- STEP 1: nodes -> segments, proportional to capacity (Fig. 3) -----
    cluster = make_cluster([1.5, 0.7, 1.0])  # TB per node, say
    print("segment table:")
    for nid, info in cluster.nodes.items():
        segs = [(s, round(float(cluster.seg_lengths()[s]), 3)) for s in info.segments]
        print(f"  node {nid} (cap {info.capacity}): segments {segs}")

    # --- STEP 2: datum id -> node -----------------------------------------
    ids = np.arange(100_000, dtype=np.uint32)
    owners = cluster.place_nodes(ids)
    frac = np.bincount(owners, minlength=3) / ids.size
    print(f"distribution: {frac.round(4)} (capacity fractions {np.array([1.5,0.7,1.0])/3.2})")

    # Pallas kernel path (interpret mode; Mosaic does not lower it on TPU yet)
    owners_k = np.asarray(
        asura_place_nodes(ids[:4096], cluster.seg_lengths(), cluster.seg_to_node())
    )
    assert np.array_equal(owners_k, owners[:4096])
    print("pallas kernel matches the oracle on 4096 ids")

    # --- optimal movement on node addition --------------------------------
    before = owners
    cluster.add_node(3, 1.0)
    after = cluster.place_nodes(ids)
    moved = before != after
    print(
        f"added node 3: {100*moved.mean():.2f}% of data moved "
        f"(ideal {100*1.0/4.2:.2f}%), all to node 3: {bool((after[moved]==3).all())}"
    )

    # --- replication + section 2.D metadata --------------------------------
    reps = cluster.place_replicas(ids[:5], 3)
    print(f"3-way replicas for first 5 ids:\n{reps}")
    lengths, node_of = cluster.seg_lengths(), cluster.seg_to_node()
    print(
        f"datum 0: ADDITION NUMBER {addition_number(0, lengths, node_of)}, "
        f"REMOVE NUMBERS {remove_numbers(0, lengths, node_of, 3)}"
    )

    # --- the shared state is just a small table ----------------------------
    blob = cluster.to_json()
    print(f"cluster table serializes to {len(blob)} bytes (memory: "
          f"{cluster.memory_bytes()} bytes for {len(cluster.nodes)} nodes)")
    clone = Cluster.from_json(blob)
    assert np.array_equal(clone.place_nodes(ids[:1000]), after[:1000])
    print("deserialized table places identically — no placement service needed")

    # --- the same interface serves the paper's baselines --------------------
    # Router(algorithm=...) swaps the placement algorithm behind the same
    # engine/artifact machinery: "ch" (consistent hashing), "wrh"
    # (capacity-weighted rendezvous) and "rs" (random slicing) all run on
    # the device-resident kernel paths (DESIGN.md section 9).
    from repro.serve import Router

    caps = {0: 1.5, 1: 0.7, 2: 1.0}
    for algorithm in ("asura", "ch", "wrh", "rs"):
        router = Router(caps, algorithm=algorithm)
        share = np.bincount(router.route(ids[:20_000]), minlength=3) / 20_000
        print(f"  {algorithm:>5} routing shares: {share.round(3)}")


if __name__ == "__main__":
    main()
