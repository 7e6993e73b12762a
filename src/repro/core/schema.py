"""Column schema of a typed RSP: the meaning of each int32 column.

A typed store keeps every record as a row of int32 codes, so that every
value is stored bit-exact and compared exactly.  The schema says what each
code means:

* ``key`` and ``int`` -- the integer itself (keys are identifiers, never
  summed);
* ``decimal`` -- a fixed-point number, ``code / 10**scale``;
* ``date`` -- days since 1970-01-01;
* ``dict`` -- an index into the column's ``dictionary`` of values;
* ``id`` -- an opaque 32-bit identifier (of a text, say).

Literals of a query are given in the column's own unit (``date
'1998-09-02'``, ``0.05``, ``'R'``) and encoded here into codes.
"""

from __future__ import annotations

import dataclasses
import datetime
import math
from typing import Any, Sequence

COLUMN_KINDS = ("key", "int", "decimal", "date", "dict", "id")
EPOCH = datetime.date(1970, 1, 1)


def date_code(day: str | datetime.date) -> int:
    """Days since 1970-01-01 of an ISO date (``'1998-09-02'``)."""
    if isinstance(day, str):
        day = datetime.date.fromisoformat(day)
    return (day - EPOCH).days


@dataclasses.dataclass(frozen=True)
class Column:
    name: str
    kind: str
    scale: int = 0
    dictionary: tuple[str, ...] = ()

    def __post_init__(self):
        if self.kind not in COLUMN_KINDS:
            raise ValueError(f"unknown column kind {self.kind!r} (one of {COLUMN_KINDS})")
        object.__setattr__(self, "dictionary", tuple(str(v) for v in self.dictionary))
        if (self.kind == "dict") != bool(self.dictionary):
            raise ValueError(f"column {self.name!r}: a dictionary belongs to kind 'dict' alone")
        if self.scale and self.kind != "decimal":
            raise ValueError(f"column {self.name!r}: a scale belongs to kind 'decimal' alone")

    def bound(self, op: str, literal: Any) -> tuple[str, int]:
        """``column <op> literal`` as the same comparison on codes.  A decimal
        literal between two codes becomes the equivalent inclusive bound."""
        if self.kind == "date":
            return op, date_code(literal)
        if self.kind == "dict":
            try:
                return op, self.dictionary.index(str(literal))
            except ValueError:
                raise ValueError(f"{literal!r} is not a value of column {self.name!r}") from None
        x = float(literal) * 10**self.scale
        code = round(x)
        if abs(x - code) <= 1e-9 * max(1.0, abs(x)):
            return op, int(code)
        if op in ("lt", "le"):
            return "le", math.floor(x)
        if op in ("gt", "ge"):
            return "ge", math.ceil(x)
        raise ValueError(f"{literal!r} is not a value of column {self.name!r} (scale {self.scale})")

    def to_dict(self) -> dict:
        d: dict = {"name": self.name, "kind": self.kind}
        if self.scale:
            d["scale"] = self.scale
        if self.dictionary:
            d["dictionary"] = list(self.dictionary)
        return d


@dataclasses.dataclass(frozen=True)
class Schema:
    """The ordered columns of a typed table; column ``i`` is field ``i`` of
    each int32 record."""

    columns: tuple[Column, ...]

    def __post_init__(self):
        cols = tuple(c if isinstance(c, Column) else Column(**c) for c in self.columns)
        object.__setattr__(self, "columns", cols)
        names = [c.name for c in cols]
        if len(set(names)) != len(names):
            raise ValueError("column names must be unique")

    def __len__(self) -> int:
        return len(self.columns)

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(c.name for c in self.columns)

    def index(self, name: str) -> int:
        try:
            return self.names.index(name)
        except ValueError:
            raise KeyError(f"no column {name!r} in the schema ({', '.join(self.names)})") from None

    def column(self, name: str) -> Column:
        return self.columns[self.index(name)]

    def to_list(self) -> list[dict]:
        return [c.to_dict() for c in self.columns]

    @classmethod
    def from_list(cls, cols: Sequence[dict]) -> "Schema":
        return cls(tuple(Column(**c) for c in cols))
