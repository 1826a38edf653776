"""Static analysis of compiled programs (docs/analysis.md).

:mod:`.program_audit` lowers jitted / shard_mapped programs and verifies
their collective structure, donation and host-sync hygiene against
declarative budgets; :mod:`.budgets` is the shared (jax-free, pure-
literal) budget registry both the runtime consumers and the repo linter
read; the linter itself is ``tools/dslint`` (``bin/dstpu_lint``).
"""

from .budgets import HOP_BUDGETS, SITE_BUDGETS, budget_args
from .program_audit import (CollectiveBudget, CollectiveSite, ProgramReport,
                            RecompileTripwire, assert_budget,
                            audit_fn, audit_serve_programs,
                            donated_arg_indices, serve_program_calls)

__all__ = [
    "CollectiveBudget", "CollectiveSite", "HOP_BUDGETS", "ProgramReport",
    "RecompileTripwire", "SITE_BUDGETS", "assert_budget", "audit_fn",
    "audit_serve_programs", "budget_args", "donated_arg_indices",
    "serve_program_calls",
]
