"""Shared pieces of the four ratio reports (q36, q53, q59, q98).

Engine side: the two things the DataFrame DSL has no spelling for, a
``CASE WHEN`` and a decimal literal of a stated type, built as the IR
nodes Spark's converter would send. Oracle side: Spark's decimal
arithmetic in Python's ``decimal`` module — the result TYPE of every
division, average and product written out from Spark's own rules
(``DecimalPrecision`` with ``adjustPrecisionScale``,
``allowPrecisionLoss`` at its default), HALF_UP, null on a zero divisor
and on overflow — importing nothing of the engine.

``double_division=True`` is the control: the same reference with every
division carried in double and rounded to the result scale, the
precision below the decimal the configuration states.
"""

from __future__ import annotations

import datetime
import decimal
from decimal import Decimal

import pyarrow as pa
import pyarrow.compute as pc

decimal.getcontext().prec = 100

#: sum(decimal(7,2)) as Spark types it
MONEY_SUM = (17, 2)

#: The generator's item key is Zipf(1.3): item 1 sells a quarter of all
#: rows, item 2 a tenth (the two it pins for the star joins), the 64
#: lowest-numbered items half. qgen draws a query's substitution values
#: from the data's own distributions; where a report's list is drawn from
#: the item table here, it is drawn past these, so that the report's size
#: (and a task's work) does not hang on whether a seed's hot item
#: happens to fall under the list.
PINNED_ITEMS = 2
HOT_ITEMS = 64


# -- engine side -------------------------------------------------------------

def case_when(df, cond, value):
    """``CASE WHEN cond THEN value END`` (no ELSE: null) over ``df``."""
    from auron_tpu.exprs import ir
    from auron_tpu.frontend.dataframe import Col, resolve
    return Col(ir.CaseWhen(((resolve(cond, df.schema),
                             resolve(value, df.schema)),), None))


def dec_lit(text: str):
    """A decimal literal typed as Spark types it: ``100`` decimal(3,0),
    ``0.1`` decimal(1,1), ``0`` decimal(1,0)."""
    from auron_tpu.columnar.schema import DataType
    from auron_tpu.exprs import ir
    from auron_tpu.frontend.dataframe import Col
    d = Decimal(text)
    scale = max(-d.as_tuple().exponent, 0)
    unscaled = int(d.scaleb(scale))
    digits = len(str(abs(unscaled)))
    return Col(ir.Literal(unscaled, DataType.DECIMAL, max(digits, scale, 1),
                          scale))


def date_lit(day: datetime.date):
    from auron_tpu.columnar.schema import DataType
    from auron_tpu.frontend.dataframe import lit
    return lit((day - datetime.date(1970, 1, 1)).days, DataType.DATE32)


# -- oracle side: Spark's decimal types and arithmetic -----------------------

def adjust(p: int, s: int) -> tuple:
    """``DecimalType.adjustPrecisionScale``."""
    if p <= 38:
        return p, s
    int_digits = p - s
    return 38, max(38 - int_digits, min(s, 6))


def divide_type(a: tuple, b: tuple) -> tuple:
    (p1, s1), (p2, s2) = a, b
    s = max(6, s1 + p2 + 1)
    return adjust(p1 - s1 + s2 + s, s)


def multiply_type(a: tuple, b: tuple) -> tuple:
    (p1, s1), (p2, s2) = a, b
    return adjust(p1 + p2 + 1, s1 + s2)


def subtract_type(a: tuple, b: tuple) -> tuple:
    (p1, s1), (p2, s2) = a, b
    s = max(s1, s2)
    return adjust(max(p1 - s1, p2 - s2) + s + 1, s)


def avg_type(a: tuple) -> tuple:
    """avg(decimal(p,s)) -> decimal(p+4, s+4), bounded at 38."""
    p, s = a
    return min(p + 4, 38), min(s + 4, 38)


def sum_type(a: tuple) -> tuple:
    p, s = a
    return min(p + 10, 38), s


def to_type(x, typ: tuple):
    """``x`` (a Decimal, exact) as decimal(p, s): HALF_UP at the scale,
    None where it overflows the precision (Spark's non-ANSI null)."""
    if x is None:
        return None
    p, s = typ
    q = x.quantize(Decimal(1).scaleb(-s), rounding=decimal.ROUND_HALF_UP)
    return q if abs(q) < Decimal(10) ** (p - s) else None


def divide(a, b, typ: tuple, double_division: bool = False):
    """Spark's Divide of two decimals into decimal ``typ``."""
    if a is None or b is None or b == 0:
        return None
    if double_division:
        return to_type(Decimal(repr(float(a) / float(b))), typ)
    return to_type(a / b, typ)


def arrow_type(typ: tuple):
    return pa.decimal128(*typ)


def decimal_column(values, typ: tuple):
    return pa.array(values, arrow_type(typ))


# -- oracle side: joins and sums in Acero ------------------------------------

def group_sums(table, keys, sums):
    """``sums`` {output name: money column} summed by ``keys`` (none: one
    row over the whole table); decimal sums are exact in Acero. A list of
    row dicts, the sums as ``Decimal`` (None over no values)."""
    cols = sorted(set(sums.values()))
    if keys:
        rows = table.group_by(keys, use_threads=False).aggregate(
            [(c, "sum") for c in cols]).to_pylist()
    else:
        rows = [{f"{c}_sum": pc.sum(table[c]).as_py() for c in cols}]
    return [{**{k: row[k] for k in keys},
             **{name: row[f"{c}_sum"] for name, c in sums.items()}}
            for row in rows]


def sort_key(*parts):
    """A total order for ORDER BY keys as Spark sorts them: ``parts`` are
    (value, ascending) pairs; ascending puts nulls first, descending
    last."""
    key = []
    for value, asc in parts:
        if value is None:
            key.append((0, 0) if asc else (1, 0))
        elif asc:
            key.append((1, value))
        else:
            key.append((0, _Neg(value)))
    return tuple(key)


class _Neg:
    """Reverses the order of whatever it wraps."""
    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v

    def __lt__(self, other):
        return other.v < self.v

    def __eq__(self, other):
        return self.v == other.v
