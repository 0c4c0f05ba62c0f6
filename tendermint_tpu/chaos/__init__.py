"""Deterministic chaos subsystem — seeded fault injection for the p2p
mesh.

The reference engine's e2e runner perturbs networks ad-hoc (kill,
disconnect, byte fuzzing); this package turns those hacks into an owned,
replayable subsystem:

- `link`    — seeded per-link network shaping (latency+jitter, drop,
              duplicate, reorder, bandwidth) interposed at the transport
              connection layer, so every reactor runs through it
              unmodified.
- `network` — a controller over running switches: named partitions,
              per-peer blackholes, link policy installation, heal.
- `scenario`— declarative seeded timelines (at height/time X: partition,
              kill, restart, skew clocks, heal) executed against in-proc
              multi-node networks; one seed replays the whole fault plan.

Env knobs: TM_TPU_CHAOS_SEED (default scenario seed).
"""

from .link import ChaosConn, FaultTrace, LinkPolicy, link_rng
from .network import ChaosNetwork
from .scenario import NodeHandle, Scenario, ScenarioRunner, Step, random_scenario

__all__ = [
    "ChaosConn",
    "ChaosNetwork",
    "FaultTrace",
    "LinkPolicy",
    "NodeHandle",
    "Scenario",
    "ScenarioRunner",
    "Step",
    "link_rng",
    "random_scenario",
]
