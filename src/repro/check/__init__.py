"""``repro.check`` — static analysis of traced programs against the repo's
structural contracts.

The paper's advantage (14/3·n^log2(7) flops, transpose-free TN leaves,
packed-symmetric output, O(levels) dispatch) only survives in a traced
program if structural invariants hold. This package turns those invariants
— previously scattered across hand-rolled test walkers — into a
rule-registry static analyzer over traced artifacts:

* :mod:`repro.check.findings` — :class:`Finding` / :class:`Allow` /
  :class:`Report`: structured violations with eqn provenance, the
  allowlist, and the JSON report (schema ``repro.check/v1``).
* :mod:`repro.check.artifacts` — :class:`Artifact`, the canonical
  :func:`walk_eqns` traversal, and :func:`trace_plan` (traces the exact
  callable the autotuner times).
* :mod:`repro.check.rules` — the registry and the seven shipped rules.
* :mod:`repro.check.harness` — the canonical plan grid and the
  distributed (multi-device) sweep.

CLI: ``python -m repro.check [--json CHECK_report.json]`` — nonzero exit
on violations; ``--distributed`` for the SPMD schedules. DESIGN.md §9 has
the rule taxonomy and the policy for allowlisting intentional violations.
"""

from repro.check.artifacts import Artifact, abstract_args, plan_label, trace_plan, walk_eqns
from repro.check.findings import Allow, Finding, Report, REPORT_SCHEMA
from repro.check.harness import (
    DEFAULT_ALLOWLIST,
    bfsdfs_plans,
    canonical_plans,
    distributed_plans,
    run_distributed,
    run_grid,
)
from repro.check.rules import REGISTRY, rule, rule_ids, run, run_many

__all__ = [
    "Artifact", "Allow", "Finding", "Report", "REPORT_SCHEMA",
    "REGISTRY", "DEFAULT_ALLOWLIST",
    "abstract_args", "plan_label", "trace_plan", "walk_eqns",
    "rule", "rule_ids", "run", "run_many",
    "canonical_plans", "run_grid", "distributed_plans", "bfsdfs_plans",
    "run_distributed",
]
