"""Query plans for the fused filter+project+sketch kernels.

A :class:`QueryPlan` is the static description the kernel templates compile
against: column predicates (conjunctive ``where``), a column projection,
and an optional group-by column.  Everything in the plan is baked into the
compiled kernel as constants -- the plan's :meth:`QueryPlan.key` is the
compile-cache key, so changing a predicate value recompiles while repeating
a plan hits the cache.

Predicate semantics are defined on the **float32 view** of the block (every
execution path -- numpy reference included -- evaluates predicates after an
``astype(float32)``), so a value that straddles the f32 rounding of the
threshold cannot flip between implementations.  Projections select columns
*after* filtering; ``group_by`` always indexes the original (pre-projection)
feature space, like ``RSPDataset.label_column`` does.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

OPS = ("lt", "le", "gt", "ge", "eq", "ne")
_NUMPY_OPS = {
    "lt": np.less,
    "le": np.less_equal,
    "gt": np.greater,
    "ge": np.greater_equal,
    "eq": np.equal,
    "ne": np.not_equal,
}
_SYMBOLS = {"<": "lt", "<=": "le", ">": "gt", ">=": "ge", "==": "eq", "!=": "ne"}

_PRED_RE = re.compile(
    r"^\s*(?:c|col)?(\d+)\s*(<=|>=|==|!=|<|>)\s*([-+]?[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?)\s*$"
)


@dataclasses.dataclass(frozen=True)
class Predicate:
    """One column comparison: ``column <op> value`` with ``op`` one of
    ``lt | le | gt | ge | eq | ne`` (symbols accepted and normalized)."""

    column: int
    op: str
    value: float

    def __post_init__(self):
        op = _SYMBOLS.get(self.op, self.op)
        if op not in OPS:
            raise ValueError(f"unknown predicate op {self.op!r} (one of {OPS} or symbols)")
        object.__setattr__(self, "op", op)
        object.__setattr__(self, "column", int(self.column))
        object.__setattr__(self, "value", float(self.value))
        if self.column < 0:
            raise ValueError("predicate column must be >= 0")

    def mask(self, x: np.ndarray) -> np.ndarray:
        """Boolean row mask over ``x`` [n, F] (float32 comparison)."""
        return _NUMPY_OPS[self.op](x[:, self.column], np.float32(self.value))

    def __str__(self) -> str:
        sym = {v: k for k, v in _SYMBOLS.items()}[self.op]
        return f"c{self.column} {sym} {self.value:g}"


def parse_predicate(spec) -> Predicate:
    """``"c3 > 0.5"`` / ``"0 <= 1e-2"`` / ``(3, ">", 0.5)`` /
    ``Predicate`` -> :class:`Predicate`."""
    if isinstance(spec, Predicate):
        return spec
    if isinstance(spec, str):
        m = _PRED_RE.match(spec)
        if not m:
            raise ValueError(
                f"cannot parse predicate {spec!r} (expected e.g. 'c3 > 0.5')"
            )
        return Predicate(int(m.group(1)), m.group(2), float(m.group(3)))
    if isinstance(spec, (tuple, list)) and len(spec) == 3:
        return Predicate(int(spec[0]), str(spec[1]), float(spec[2]))
    raise TypeError(f"cannot build a Predicate from {type(spec).__name__}")


def as_predicates(where) -> tuple[Predicate, ...]:
    """Normalize a ``where=`` argument -- ``None``, one predicate spec, or a
    sequence of them -- into a tuple of :class:`Predicate` (AND semantics)."""
    if where is None:
        return ()
    if isinstance(where, (str, Predicate)):
        return (parse_predicate(where),)
    if isinstance(where, (tuple, list)):
        if len(where) == 3 and isinstance(where[0], (int, np.integer)):
            return (parse_predicate(where),)
        return tuple(parse_predicate(p) for p in where)
    raise TypeError(f"cannot build predicates from {type(where).__name__}")


@dataclasses.dataclass(frozen=True)
class QueryPlan:
    """The static shape of one fused block pass.

    ``predicates`` AND together (empty = all rows).  ``columns`` projects
    the sketch onto those original-space columns (``None`` = all).
    ``group_by``/``num_classes`` produce one sketch per class from the
    ``group_by`` column of the *original* feature space; ungrouped plans
    leave ``group_by=None`` and ``num_classes=1``.
    """

    predicates: tuple[Predicate, ...] = ()
    columns: tuple[int, ...] | None = None
    group_by: int | None = None
    num_classes: int = 1

    def __post_init__(self):
        object.__setattr__(self, "predicates", as_predicates(self.predicates))
        if self.columns is not None:
            object.__setattr__(
                self, "columns", tuple(int(c) for c in self.columns)
            )
            if len(self.columns) == 0:
                raise ValueError("columns= must name at least one column")
        if self.group_by is None:
            if self.num_classes != 1:
                raise ValueError("num_classes needs group_by (or must be 1)")
        elif self.num_classes < 1:
            raise ValueError("grouped plans need num_classes >= 1")

    @property
    def groups(self) -> int:
        return self.num_classes if self.group_by is not None else 1

    @property
    def filtered(self) -> bool:
        return bool(self.predicates)

    def key(self) -> tuple:
        """Hashable identity for the compile cache: two plans with the same
        key compile to the same kernel."""
        return (
            tuple((p.column, p.op, p.value) for p in self.predicates),
            self.columns,
            self.group_by,
            self.num_classes,
        )

    def resolve_columns(self, num_features: int) -> tuple[int, ...]:
        """The projected column indices against an ``[n, F]`` block."""
        if self.columns is None:
            return tuple(range(num_features))
        cols = tuple(c % num_features for c in self.columns)
        return cols

    def mask(self, x: np.ndarray) -> np.ndarray:
        """AND of all predicate masks over float32 ``x`` [n, F]."""
        if not self.predicates:
            return np.ones(x.shape[0], dtype=bool)
        m = self.predicates[0].mask(x)
        for p in self.predicates[1:]:
            m &= p.mask(x)
        return m


# ---------------------------------------------------------------------------
# Typed plans: int32 tables with a column schema (repro.core.schema)
# ---------------------------------------------------------------------------

_NAMED_RE = re.compile(
    r"^\s*([A-Za-z_]\w*)\s*(<=|>=|==|!=|<|>)\s*"
    r"(?:date\s*'(\d{4}-\d{2}-\d{2})'|'([^']*)'|([-+]?[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?))\s*$"
)


@dataclasses.dataclass(frozen=True)
class NamedPredicate:
    """``name <op> literal`` over a typed table, the literal in the column's
    own unit: ``"l_shipdate <= date '1998-09-02'"``, ``"l_discount >= 0.05"``,
    ``"l_returnflag == 'R'"``.  Bound to codes by :func:`bind_plan`."""

    name: str
    op: str
    literal: str | float

    def __post_init__(self):
        op = _SYMBOLS.get(self.op, self.op)
        if op not in OPS:
            raise ValueError(f"unknown predicate op {self.op!r} (one of {OPS} or symbols)")
        object.__setattr__(self, "op", op)


def parse_where(spec):
    """One ``where=`` item: a float :class:`Predicate` (``"c3 > 0.5"``) or a
    :class:`NamedPredicate` (``"l_quantity < 24"``, ``("l_tax", "<", 0.02)``)."""
    if isinstance(spec, (Predicate, NamedPredicate)):
        return spec
    if isinstance(spec, str):
        if _PRED_RE.match(spec):
            return parse_predicate(spec)
        m = _NAMED_RE.match(spec)
        if not m:
            raise ValueError(
                f"cannot parse predicate {spec!r} (expected e.g. 'c3 > 0.5' or"
                " \"l_shipdate <= date '1998-09-02'\")"
            )
        date, text, number = m.group(3), m.group(4), m.group(5)
        literal = date if date is not None else text if text is not None else float(number)
        return NamedPredicate(m.group(1), m.group(2), literal)
    if isinstance(spec, (tuple, list)) and len(spec) == 3 and isinstance(spec[0], str):
        return NamedPredicate(spec[0], str(spec[1]), spec[2])
    return parse_predicate(spec)


def as_where(where) -> tuple:
    """Normalize ``where=`` (None, one predicate, or a sequence of them, AND
    semantics) into a tuple of :class:`Predicate` / :class:`NamedPredicate`."""
    if where is None:
        return ()
    single = isinstance(where, (str, Predicate, NamedPredicate)) or (
        isinstance(where, (tuple, list)) and len(where) == 3
        and isinstance(where[0], (str, int, np.integer)) and where[1] in (*OPS, *_SYMBOLS)
    )
    if single:
        return (parse_where(where),)
    if isinstance(where, (tuple, list)):
        return tuple(parse_where(p) for p in where)
    raise TypeError(f"cannot build predicates from {type(where).__name__}")


@dataclasses.dataclass(frozen=True)
class CodePredicate:
    """``column <op> code``, compared exactly on a typed table's int32 codes."""

    column: int
    op: str
    code: int

    def mask(self, x: np.ndarray) -> np.ndarray:
        return _NUMPY_OPS[self.op](x[:, self.column], np.int32(self.code))


FORMS = ("x", "1-x", "1+x")
_FACTOR_RE = re.compile(r"^\s*(?:([A-Za-z_]\w*)|\(\s*1\s*([-+])\s*([A-Za-z_]\w*)\s*\))\s*$")


@dataclasses.dataclass(frozen=True)
class Factor:
    """One factor of a measure: column ``column`` decoded by ``10**-scale``
    (``x``), or ``1 - x`` / ``1 + x``."""

    column: int
    scale: int
    form: str = "x"


def parse_expr(expr: str) -> tuple[tuple[str, str], ...]:
    """``"l_extendedprice * (1 - l_discount) * (1 + l_tax)"`` ->
    ``(("l_extendedprice", "x"), ("l_discount", "1-x"), ("l_tax", "1+x"))``:
    a product of columns, each as it is or as ``(1 - c)`` / ``(1 + c)``."""
    out = []
    for part in expr.split("*"):
        m = _FACTOR_RE.match(part)
        if not m:
            raise ValueError(
                f"cannot parse measure {expr!r}: expected a product of columns, each"
                " 'name', '(1 - name)' or '(1 + name)'"
            )
        out.append((m.group(1), "x") if m.group(1) else (m.group(3), f"1{m.group(2)}x"))
    return tuple(out)


def decode_measures(column, measures, xp=np) -> list:
    """Each measure's float32 values, from ``column(c)``, the int32 codes of
    column ``c`` (any shape).  The one definition of the typed arithmetic:
    codes to float32 times ``10**-scale``, then ``1 -+ x``, then the product
    in the factors' order; every implementation computes it alike."""
    out = []
    for factors in measures:
        value = None
        for f in factors:
            v = column(f.column).astype(xp.float32) * np.float32(10.0 ** -f.scale)
            if f.form == "1-x":
                v = 1.0 - v
            elif f.form == "1+x":
                v = 1.0 + v
            value = v if value is None else value * v
        out.append(value)
    return out


def group_keys(column, group_by):
    """Mixed-radix group key of each row from the dictionary codes of the
    ``group_by`` columns (``(column, cardinality)``, the first most
    significant); 0 for an ungrouped plan."""
    key = None
    for c, card in group_by:
        key = column(c) if key is None else key * int(card) + column(c)
    return 0 if key is None else key


@dataclasses.dataclass(frozen=True)
class TypedPlan:
    """The static shape of one typed fold over int32 blocks: code
    predicates (AND), measures (each a tuple of :class:`Factor`, summed per
    group) and a group-by over dictionary columns (``(column,
    cardinality)``; the group of a row is the mixed radix of its codes).
    Per group a fold gives the count of selected rows and each measure's
    float32 sum."""

    predicates: tuple[CodePredicate, ...] = ()
    measures: tuple[tuple[Factor, ...], ...] = ()
    group_by: tuple[tuple[int, int], ...] = ()

    @property
    def groups(self) -> int:
        g = 1
        for _, card in self.group_by:
            g *= card
        return g

    def key(self) -> tuple:
        return ("typed", self.predicates, self.measures, self.group_by)

    def mask(self, x: np.ndarray) -> np.ndarray:
        m = np.ones(x.shape[0], dtype=bool)
        for p in self.predicates:
            m &= p.mask(x)
        return m


def bind_plan(schema, where=(), exprs=(), group_by=()) -> TypedPlan:
    """The :class:`TypedPlan` of named predicates, measure expressions and
    group-by column names over a table with ``schema``."""
    preds = []
    for p in where:
        if not isinstance(p, NamedPredicate):
            raise ValueError(f"a typed table takes named predicates, not {p}")
        col = schema.column(p.name)
        op, code = col.bound(p.op, p.literal)
        preds.append(CodePredicate(schema.index(p.name), op, code))
    measures = []
    for expr in exprs:
        factors = []
        for name, form in parse_expr(expr):
            col = schema.column(name)
            if col.kind not in ("int", "decimal"):
                raise ValueError(f"column {name!r} ({col.kind}) is not a measure")
            factors.append(Factor(schema.index(name), col.scale, form))
        measures.append(tuple(factors))
    keys = []
    for name in group_by:
        col = schema.column(name)
        if col.kind != "dict":
            raise ValueError(f"group_by needs dictionary columns, {name!r} is {col.kind}")
        keys.append((schema.index(name), len(col.dictionary)))
    return TypedPlan(tuple(preds), tuple(measures), tuple(keys))
