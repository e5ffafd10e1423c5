"""Device-resident migration subsystem (DESIGN.md section 8).

Three layers over one membership change v -> v+1:

  1. ``MigrationPlanner``  -- streaming version-diff planner: places every
     id under both cached table versions in one device pass (fused
     dual-table kernel, owner prefilter for add-node events) and
     emits the minimal ``MigrationPlan``.
  2. ``ThrottledMover``    -- drains the plan in rounds under per-node
     ingress/egress budgets (simulated clock), maintaining the landed
     bitmap in ``MigrationState`` and per-round movement matrices.
  3. ``LiveMigration``     -- dual-version serving: routes every read to
     the node that actually holds the datum mid-drain (v owner while the
     move is pending, v+1 owner after it lands), host and device paths,
     with free rollback of half-landed migrations.

The unit of work is a replica SLOT (DESIGN.md section 10): plan rows are
``(id, replica_slot, src, dst)``, the landed bitmap is per slot, and
``LiveMigration.route_replicas[_device]`` serves mixed-version replica
sets -- each slot independently v or v+1 by its own landed bit --
reproducing the paper's minimal data movement *even if data are
replicated* (characteristic 1).  Single-owner migration is the R=1 case.
The round/pump/run drain loop all four driver layers share lives in
``drain.DrainDriver``.

Consumers: ``runtime.elastic`` (live add/remove, R-way owner tracking),
``runtime.failures`` (failure -> throttled replica repair), ``serve.router``
(serve through a scale event, replica fan-out included),
``checkpoint.sharded`` (read-through per-slot blob migration and live
node repair).
"""

from .drain import DrainDriver
from .live import LiveMigration
from .mover import MigrationState, ThrottledMover
from .planner import DEFAULT_CHUNK, MigrationPlan, MigrationPlanner

__all__ = [
    "DEFAULT_CHUNK",
    "DrainDriver",
    "LiveMigration",
    "MigrationPlan",
    "MigrationPlanner",
    "MigrationState",
    "ThrottledMover",
]
