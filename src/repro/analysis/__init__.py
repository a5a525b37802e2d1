"""repro.analysis — static program-contract checker (DESIGN.md §11).

The paper's headline claims survive in this repro as *structural program
properties* (no materialized [B, T, N] state tensor, one Pallas launch pair
per chunk, donated serving slabs, no silent dtype widening).  This package
turns each property into a declarative `Rule` evaluated against traced
jaxprs / lowered StableHLO — no execution — and registers every compiled
entry point with its contract set.  ``python -m repro.analysis`` checks them
all and writes ANALYSIS_report.json.

`walker` is the provenance-carrying jaxpr walker (the promoted successor of
``repro.pipeline.introspect``, which re-exports from here), `rules` the
contract catalog, `registry` the entry points, `cli` the gate.
"""

from .rules import (CALLBACK_PRIMS, VMEM_BYTES, DonationHonored,
                    MaxPallasCalls, MaxScans, NoDtypeAbove, NoHostCallback,
                    NoSilentUpcast, NoStateTensor, Program, Rule, Violation,
                    VmemBudget, check_rules, mosaic_kernels)
from .walker import (Intermediate, count_pallas_calls, count_scans,
                     eqn_paths, intermediate_records, intermediate_shapes,
                     max_intermediate_bytes, pallas_eqns,
                     state_tensor_bytes, state_tensor_records, trace_jaxpr,
                     walk_eqns, walk_eqns_with_path)

__all__ = [
    "CALLBACK_PRIMS", "VMEM_BYTES", "DonationHonored", "Intermediate",
    "MaxPallasCalls", "MaxScans", "NoDtypeAbove", "NoHostCallback",
    "NoSilentUpcast", "NoStateTensor", "Program", "Rule", "Violation",
    "VmemBudget", "check_rules", "count_pallas_calls", "count_scans",
    "eqn_paths", "intermediate_records", "intermediate_shapes",
    "max_intermediate_bytes", "mosaic_kernels", "pallas_eqns", "state_tensor_bytes",
    "state_tensor_records", "trace_jaxpr", "walk_eqns",
    "walk_eqns_with_path",
]
