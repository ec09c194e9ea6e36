"""The reference for statements whose answers hold a column the
generator keeps no pandas form of: the generated ``c_name`` is, as the
TPC-H specification defines it (clause 4.2.3), ``Customer#`` followed by
``c_custkey`` in nine digits, so it is made here from the key and the
statement's reference in ``tpch_oracle`` runs on the frames with it."""

from __future__ import annotations

import tpch_oracle


def _with_customer_names(frames: dict) -> dict:
    out = dict(frames)
    customer = frames["customer"].copy()
    customer["c_name"] = "Customer#" + \
        customer["c_custkey"].astype(str).str.zfill(9)
    out["customer"] = customer
    return out


def q18(frames: dict):
    return tpch_oracle.q18(_with_customer_names(frames))
