"""Plan-compiled fused query kernels: predicate filter + column projection
+ grouped Chan-moment/histogram sketch in one data pass, compiled per
:class:`QueryPlan` and dispatched through the shared tile autotuner; and
the typed fold of a :class:`TypedPlan` over int32 blocks (exact code
predicates, expression sums, multi-column dictionary group-by)."""

from repro.kernels.plan.ops import (
    IMPLS,
    cache_clear,
    cache_info,
    compile_plan,
    plan_sketch,
    plan_totals,
)
from repro.kernels.plan.plan import (
    CodePredicate,
    Factor,
    NamedPredicate,
    Predicate,
    QueryPlan,
    TypedPlan,
    as_predicates,
    as_where,
    bind_plan,
    parse_expr,
    parse_predicate,
)
from repro.kernels.plan.ref import (
    PlanResult,
    PlanTotals,
    empty_sketch,
    plan_sketch_ref,
    plan_totals_ref,
)

__all__ = [
    "IMPLS",
    "CodePredicate",
    "Factor",
    "NamedPredicate",
    "PlanResult",
    "PlanTotals",
    "Predicate",
    "QueryPlan",
    "TypedPlan",
    "as_predicates",
    "as_where",
    "bind_plan",
    "cache_clear",
    "cache_info",
    "compile_plan",
    "empty_sketch",
    "parse_expr",
    "parse_predicate",
    "plan_sketch",
    "plan_sketch_ref",
    "plan_totals",
    "plan_totals_ref",
]
