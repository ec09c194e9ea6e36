"""The comparison that decides ``correct``: every answer the timed
window produced against the plain reference (``reference/``, pandas on
the generated frames), plus the control that has to fail it.

The numbers compared, each with a limit of its own in the
configuration's ``limits``:

- ``row_count_mismatches``: answers with another number of rows than
  the reference's;
- ``exact_mismatches``: cells of integer, date and string columns that
  differ from the reference's (a column of the wrong kind counts whole);
- ``worst_rel_err``: the largest ``|got - ref| / max(|ref|, 1)`` over
  the decimal and double columns.

Nothing here imports the program.
"""

from __future__ import annotations

import importlib
import os
import sys

import numpy as np
import pandas as pd

HERE = os.path.dirname(os.path.abspath(__file__))


def reference_function(spec: str):
    """``module:function`` under ``reference/``."""
    module, _, function = spec.partition(":")
    ref_dir = os.path.join(HERE, "reference")
    if ref_dir not in sys.path:
        sys.path.insert(0, ref_dir)
    return getattr(importlib.import_module(module), function)


def normalize(df: pd.DataFrame) -> pd.DataFrame:
    """Positional column names, one dtype per kind."""
    out = df.copy()
    out.columns = [f"c{i}" for i in range(len(out.columns))]
    for c in out.columns:
        kind = out[c].dtype.kind
        if kind == "M":
            out[c] = pd.to_datetime(out[c]).astype("datetime64[us]")
        elif kind in "iu":
            out[c] = out[c].astype(np.int64)
        elif kind == "f":
            out[c] = out[c].astype(np.float64)
        elif kind in "OUT":
            out[c] = out[c].astype(object)
    return out.reset_index(drop=True)


def answer_frame(table) -> pd.DataFrame:
    """An Arrow answer as pandas: decimals as float64 (the nearest
    double to the decimal's value), dates as datetime64."""
    import pyarrow as pa
    cols = []
    for col in table.columns:
        if pa.types.is_decimal(col.type):
            col = col.cast(pa.float64())
        elif pa.types.is_date(col.type):
            col = col.cast(pa.timestamp("us"))
        elif pa.types.is_dictionary(col.type):
            col = col.cast(col.type.value_type)
        cols.append(col)
    return normalize(pa.table(cols, names=table.column_names).to_pandas())


def reference_answer(query: dict, frames: dict) -> pd.DataFrame:
    return normalize(reference_function(query["reference"])(frames))


def _canonical(df: pd.DataFrame) -> pd.DataFrame:
    """Row order for answers whose sort keys tie: by a rounded copy, so
    float noise cannot reorder rows."""
    key = df.copy()
    for c in key.columns:
        if key[c].dtype.kind == "f":
            key[c] = key[c].round(2)
    order = key.sort_values(list(key.columns)).index
    return df.loc[order].reset_index(drop=True)


def compare_frames(got: pd.DataFrame, exp: pd.DataFrame,
                   ordered: bool = True) -> dict:
    """One answer against the reference's; counts, never raises."""
    out = {"row_count_mismatches": 0, "exact_mismatches": 0,
           "worst_rel_err": 0.0}
    if len(got) != len(exp) or len(got.columns) != len(exp.columns):
        out["row_count_mismatches"] = 1
        return out
    if not ordered:
        got, exp = _canonical(got), _canonical(exp)
    for c in exp.columns:
        g, e = got[c], exp[c]
        if g.dtype.kind != e.dtype.kind:
            out["exact_mismatches"] += len(e)
        elif e.dtype.kind == "f":
            gv, ev = g.to_numpy(), e.to_numpy()
            both_nan = np.isnan(gv) & np.isnan(ev)
            err = np.abs(gv - ev) / np.maximum(np.abs(ev), 1.0)
            err = np.where(both_nan, 0.0, np.where(np.isnan(err), np.inf,
                                                   err))
            if len(err):
                out["worst_rel_err"] = max(out["worst_rel_err"],
                                           float(err.max()))
        else:
            same = (g == e) | (g.isna() & e.isna())
            out["exact_mismatches"] += int((~same).sum())
    return out


def merge(total: dict, one: dict) -> dict:
    for k, v in one.items():
        total[k] = max(total.get(k, 0.0), v) if k == "worst_rel_err" \
            else total.get(k, 0) + v
    return total


def compare_answers(answers: list, queries: dict, frames: dict) -> dict:
    """Every answer of the window (``answers`` = [(query name, Arrow
    table)]) against the reference, which runs once per query. Answers
    of one query that are equal bit for bit are converted once."""
    total = {"row_count_mismatches": 0, "exact_mismatches": 0,
             "worst_rel_err": 0.0}
    expected, seen = {}, {}
    for name, table in answers:
        if name not in expected:
            expected[name] = reference_answer(queries[name], frames)
            seen[name] = []
        for earlier, verdict in seen[name]:
            if table.equals(earlier):
                break
        else:
            verdict = compare_frames(answer_frame(table), expected[name],
                                     queries[name].get("ordered", True))
            seen[name].append((table, verdict))
        merge(total, verdict)
    return total


# -- the control ------------------------------------------------------------

def lower_precision_frames(frames: dict) -> dict:
    """The frames with every float64 column in float32: the nearest
    precision below the one the configuration's decimals and doubles
    are held to."""
    out = {}
    for name, df in frames.items():
        df = df.copy()
        for c in df.columns:
            if df[c].dtype == np.float64:
                df[c] = df[c].astype(np.float32)
        out[name] = df
    return out


def control_reading(queries: dict, frames: dict) -> dict:
    """The control's numbers: the reference computed on float32 frames
    and put in the program's place, held to the float64 reference. It
    has to come out as not correct."""
    total = {"row_count_mismatches": 0, "exact_mismatches": 0,
             "worst_rel_err": 0.0}
    low = lower_precision_frames(frames)
    for q in queries.values():
        merge(total, compare_frames(reference_answer(q, low),
                                    reference_answer(q, frames),
                                    q.get("ordered", True)))
    return total


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: [number, limit]}); a number with no limit is an
    error of the configuration file."""
    checks = {}
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"the configuration gives no limit for {name!r}")
        checks[name] = [value, limits[name]]
    ok = all(np.isfinite(v) and v <= lim for v, lim in checks.values())
    return bool(ok), checks
