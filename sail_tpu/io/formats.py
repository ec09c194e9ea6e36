"""File format readers/writers (host side, pyarrow-backed).

Reference role: sail-data-source's TableFormat implementations
(crates/sail-data-source/src/formats/). The host decodes files to Arrow;
the columnar layer uploads to HBM. Scan-level projection/predicate pushdown
happens here (column selection + parquet row-group pruning).
"""

from __future__ import annotations

import glob as globmod
import os
from typing import Dict, List, Optional, Sequence, Tuple

import pyarrow as pa
import pyarrow.csv as pacsv
import pyarrow.dataset as pads
import pyarrow.json as pajson
import pyarrow.parquet as pq

from ..columnar.arrow_interop import arrow_type_to_spec, spec_type_to_arrow
from ..spec import data_type as dt


def expand_paths(paths: Sequence[str]) -> List[str]:
    from .cache import LISTING_CACHE

    cached = LISTING_CACHE.get(paths)
    if cached is not None:
        return cached
    out: List[str] = []
    for p in paths:
        from .object_store import has_remote_scheme
        if has_remote_scheme(p):
            out.append(p)  # remote stores list lazily via their filesystem
            continue
        if any(ch in p for ch in "*?["):
            out.extend(sorted(globmod.glob(p)))
        elif os.path.isdir(p):
            for root, _, files in os.walk(p):
                for f in sorted(files):
                    if not f.startswith((".", "_")):
                        out.append(os.path.join(root, f))
        else:
            out.append(p)
    LISTING_CACHE.put(paths, out)
    return out


def infer_schema(fmt: str, paths: Sequence[str], options: Dict[str, str]) -> dt.StructType:
    """Column names and types of the files at ``paths``; the first file
    decides. A format that carries its schema is asked for it (Parquet:
    the footer; Delta, Iceberg: the table's metadata). csv, json, text,
    binaryfile, avro and the Arrow IPC formats have their types from the
    decoded data, and a prefix can infer differently from the whole
    file, so their first file is decoded."""
    fmt = fmt.lower()
    if fmt == "delta":
        from ..lakehouse.delta import DeltaTable
        return DeltaTable(paths[0]).snapshot(
            *_delta_travel(options)).schema
    if fmt == "iceberg":
        from ..lakehouse.iceberg import IcebergTable
        opts = {k.lower(): v for k, v in options.items()}
        return IcebergTable(
            paths[0],
            metadata_location=opts.get("metadata_location")).schema()
    files = expand_paths(paths)
    if not files:
        raise FileNotFoundError(f"no files found for {paths}")
    from .. import tracing as tr
    tr.set_attribute("files", len(files))
    if fmt == "parquet":
        schema, footer_bytes = _parquet_footer_schema(files[0], options)
        tr.set_attribute("schema_source", "footer")
        tr.set_attribute("bytes_read", footer_bytes)
    else:
        schema = read_table(fmt, files[:1], options, limit=1000).schema
        tr.set_attribute("schema_source", "data")
        try:
            tr.set_attribute("bytes_read", os.path.getsize(files[0]))
        except OSError:
            pass
    return dt.StructType(tuple(
        dt.StructField(f.name, arrow_type_to_spec(f.type), True)
        for f in schema))


def _parquet_footer_schema(path: str, options: Dict[str, str]
                           ) -> Tuple[pa.Schema, int]:
    """→ (Arrow schema, bytes of footer metadata) of one Parquet file,
    local or remote, with no column decoded. ``schema_arrow`` is the
    reader's own schema (a stored ``ARROW:schema`` honoured), so it
    equals ``pq.read_table(path).schema``."""
    from .object_store import resolve_filesystem
    fsys, rel = resolve_filesystem(path, options)
    with pq.ParquetFile(rel, filesystem=fsys) as pf:
        return pf.schema_arrow, pf.metadata.serialized_size


def iso_to_ms(ts: str) -> int:
    """ISO timestamp string -> epoch millis (naive values default to
    UTC — the shared time-travel convention for Delta and Iceberg)."""
    import datetime

    dtv = datetime.datetime.fromisoformat(ts)
    if dtv.tzinfo is None:
        dtv = dtv.replace(tzinfo=datetime.timezone.utc)
    return int(dtv.timestamp() * 1000)


def _delta_travel(options: Dict[str, str]):
    opts = {k.lower(): v for k, v in options.items()}
    version = opts.get("versionasof")
    ts = opts.get("timestampasof")
    ts_ms = iso_to_ms(ts) if ts is not None else None
    return (int(version) if version is not None else None), ts_ms


_ROW_GROUP_PRUNING: Optional[bool] = None


def row_group_pruning_enabled() -> bool:
    """``parquet.enable_row_group_pruning``, read once per process —
    the gate sits on every parquet scan, so the config layer must not
    ride each one."""
    global _ROW_GROUP_PRUNING
    if _ROW_GROUP_PRUNING is None:
        try:
            from ..config import truthy
            _ROW_GROUP_PRUNING = truthy("parquet.enable_row_group_pruning")
        except Exception:  # noqa: BLE001 — default on
            _ROW_GROUP_PRUNING = True
    return _ROW_GROUP_PRUNING


def rex_predicates_to_arrow(predicates, schema) -> Optional["pads.Expression"]:
    """Scan predicates (col-vs-literal conjuncts) → a pyarrow dataset
    filter for parquet row-group/fragment pruning. Returns None when any
    conjunct fails to convert (pruning is best-effort; the exact filter
    runs above the scan). Parquet call sites gate on
    :func:`row_group_pruning_enabled`; host-side consumers (in-memory
    runtime-filter application) are unaffected by that parquet knob."""
    from ..plan import rex as rx

    def field(r):
        return pads.field(schema[r.index].name)

    def lit(r):
        return r.value.value

    out = None
    for c in predicates:
        try:
            if c.fn in ("==", "!=", "<", "<=", ">", ">="):
                a, b = c.args
                flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}
                op = c.fn
                if isinstance(a, rx.RLit):
                    a, b = b, a
                    op = flip.get(op, op)
                fa, vb = field(a), lit(b)
                expr = {"==": fa == vb, "!=": fa != vb, "<": fa < vb,
                        "<=": fa <= vb, ">": fa > vb, ">=": fa >= vb}[op]
            elif c.fn == "isnull":
                expr = field(c.args[0]).is_null()
            elif c.fn == "isnotnull":
                expr = ~field(c.args[0]).is_null()
            elif c.fn == "in":
                expr = field(c.args[0]).isin([lit(a) for a in c.args[1:]])
            elif c.fn == "rtf_member":
                # runtime join filter: exact build-side key membership
                from ..plan.runtime_filters import member_values
                ref = c.args[0]
                expr = field(ref).isin(
                    member_values(c, schema[ref.index].dtype))
            else:
                return None
        except Exception:  # noqa: BLE001 — pruning is best-effort
            return None
        out = expr if out is None else out & expr
    return out


def read_table(fmt: str, paths: Sequence[str], options: Dict[str, str],
               columns: Optional[Sequence[str]] = None,
               limit: Optional[int] = None,
               filter_expr=None) -> pa.Table:
    """Decode ``paths`` to one Arrow table. ``limit`` slices the table
    after every file has been decoded whole: it bounds what is returned,
    not what is read (``infer_schema`` uses it for the formats whose
    types come from the data)."""
    from .. import faults
    fmt = fmt.lower()
    faults.inject("io.read", key=fmt)
    if fmt == "delta":
        from ..lakehouse.delta import DeltaTable
        version, ts_ms = _delta_travel(options)
        return DeltaTable(paths[0]).to_arrow(version, ts_ms,
                                             columns=columns)
    if fmt == "iceberg":
        from ..lakehouse.iceberg import IcebergTable
        opts = {k.lower(): v for k, v in options.items()}
        sid = opts.get("snapshot-id", opts.get("snapshotid"))
        ts = opts.get("as-of-timestamp", opts.get("asoftimestamp"))
        if sid is not None:
            try:
                sid = int(sid)
            except ValueError:
                pass  # named ref (branch/tag) — resolved by snapshot()
        return IcebergTable(
            paths[0],
            metadata_location=opts.get("metadata_location")).to_arrow(
            sid, int(ts) if ts is not None else None, columns=columns)
    files = expand_paths(paths)
    from .object_store import has_remote_scheme, resolve_filesystem
    if fmt == "parquet" and files and has_remote_scheme(files[0]):
        fsys, rel = resolve_filesystem(files[0], options)
        rels = [resolve_filesystem(f, options)[1] for f in files]
        ds = pads.dataset(rels, format="parquet", filesystem=fsys)
        table = ds.to_table(columns=list(columns) if columns else None,
                            filter=filter_expr)
        if limit is not None:
            table = table.slice(0, limit)
        return table
    if fmt == "parquet":
        if filter_expr is not None:
            # dataset scan: parquet row-group + fragment pruning on
            # statistics before any decode
            ds = pads.dataset(files, format="parquet")
            table = ds.to_table(columns=list(columns) if columns else None,
                                filter=filter_expr)
        else:
            tables = [pq.read_table(f,
                                    columns=list(columns) if columns
                                    else None)
                      for f in files]
            table = pa.concat_tables(tables, promote_options="permissive") \
                if len(tables) > 1 else tables[0]
    elif fmt == "csv":
        header = options.get("header", "false").lower() in ("true", "1")
        delim = options.get("sep", options.get("delimiter", ","))
        read_opts = pacsv.ReadOptions(autogenerate_column_names=not header)
        parse_opts = pacsv.ParseOptions(delimiter=delim)
        conv = pacsv.ConvertOptions(
            include_columns=list(columns) if columns else None,
            strings_can_be_null=True,
            null_values=[options.get("nullvalue", "")] if "nullvalue" in options else [""])
        tables = [pacsv.read_csv(f, read_opts, parse_opts, conv) for f in files]
        table = pa.concat_tables(tables, promote_options="permissive") \
            if len(tables) > 1 else tables[0]
        if not header:
            table = table.rename_columns([f"_c{i}" for i in range(table.num_columns)])
    elif fmt == "json":
        tables = [pajson.read_json(f) for f in files]
        table = pa.concat_tables(tables, promote_options="permissive") \
            if len(tables) > 1 else tables[0]
        if columns:
            table = table.select(list(columns))
    elif fmt in ("arrow", "ipc", "feather"):
        import pyarrow.feather as feather
        tables = [feather.read_table(f, columns=list(columns) if columns else None)
                  for f in files]
        table = pa.concat_tables(tables) if len(tables) > 1 else tables[0]
    elif fmt == "avro":
        from .avro_format import read_avro
        table = read_avro(files, columns)
    elif fmt in ("text", "binaryfile", "binary"):
        rows = []
        for f in files:
            with open(f, "rb") as fh:
                content = fh.read()
            if fmt == "text":
                rows.extend(content.decode("utf-8", "replace").splitlines())
            else:
                rows.append(content)
        table = pa.table({"value": pa.array(rows)})
    else:
        raise ValueError(f"unsupported format {fmt!r}")
    if limit is not None:
        table = table.slice(0, limit)
    return table


def write_table(table: pa.Table, fmt: str, path: str, mode: str = "error",
                options: Optional[Dict[str, str]] = None,
                partition_by: Sequence[str] = ()):
    from .cache import invalidate_listings
    invalidate_listings()  # any engine write changes listings
    options = options or {}
    fmt = fmt.lower()
    if fmt == "noop":
        return  # reference: the noop sink discards its input
    if fmt == "console":
        # reference: console sink prints batches (show-string style)
        n = int(options.get("numrows", "20"))
        print(table.slice(0, n).to_pandas().to_string(index=False))
        if table.num_rows > n:
            print(f"... ({table.num_rows - n} more rows)")
        return
    if fmt == "iceberg":
        from ..lakehouse.iceberg import IcebergTable
        t = IcebergTable(path)
        if not IcebergTable.exists(path):
            nonempty = os.path.isdir(path) and os.listdir(path)
            if nonempty and mode == "error":
                raise FileExistsError(
                    f"path exists and is not an Iceberg table: {path}")
            if nonempty and mode == "ignore":
                return
            if nonempty and mode == "append":
                raise FileNotFoundError(
                    f"cannot append: not an Iceberg table: {path}")
            t.create(table, partition_by)
            return
        if mode == "error":
            raise FileExistsError(f"Iceberg table already exists: {path}")
        if mode == "ignore":
            return
        if mode == "append":
            t.append(table)
        else:
            t.overwrite(table)
        return
    if fmt == "delta":
        from ..lakehouse.delta import DeltaTable
        t = DeltaTable(path)
        if not DeltaTable.exists(path):
            nonempty = os.path.isdir(path) and os.listdir(path)
            if nonempty and mode == "error":
                raise FileExistsError(
                    f"path exists and is not a Delta table: {path}")
            if nonempty and mode == "ignore":
                return
            if nonempty and mode == "append":
                raise FileNotFoundError(
                    f"cannot append: not a Delta table: {path}")
            t.create(table, partition_by)
            return
        if mode == "error":
            raise FileExistsError(f"Delta table already exists: {path}")
        if mode == "ignore":
            return
        if mode == "append":
            t.append(table)
        else:
            t.overwrite(table)
        return
    exists = os.path.exists(path) and (os.listdir(path) if os.path.isdir(path) else True)
    if mode == "error" and exists:
        raise FileExistsError(f"path already exists: {path}")
    if mode == "ignore" and exists:
        return
    if mode == "overwrite" and os.path.isdir(path):
        import shutil
        shutil.rmtree(path)
    os.makedirs(path, exist_ok=True)
    if partition_by:
        if fmt == "avro":
            raise NotImplementedError(
                "partitionBy is not supported for avro writes")
        pads.write_dataset(table, path, format=_ds_format(fmt),
                           partitioning=list(partition_by),
                           partitioning_flavor="hive",
                           existing_data_behavior="overwrite_or_ignore")
        return
    import uuid
    fname = f"part-00000-{uuid.uuid4().hex}.{fmt if fmt != 'json' else 'json'}"
    fpath = os.path.join(path, fname)
    if fmt == "parquet":
        compression = options.get("compression")
        if compression is None:
            from ..config import get as config_get
            compression = str(config_get("parquet.compression", "snappy"))
        pq.write_table(table, fpath, compression=compression)
    elif fmt == "csv":
        header = options.get("header", "false").lower() in ("true", "1")
        pacsv.write_csv(table, fpath,
                        pacsv.WriteOptions(include_header=header))
    elif fmt == "json":
        with open(fpath, "w") as fh:
            for row in table.to_pylist():
                import json as jsonmod
                fh.write(jsonmod.dumps(row, default=str) + "\n")
    elif fmt in ("arrow", "ipc", "feather"):
        import pyarrow.feather as feather
        feather.write_feather(table, fpath)
    elif fmt == "avro":
        from .avro_format import write_avro
        write_avro(table, fpath)
    else:
        raise ValueError(f"unsupported write format {fmt!r}")


def _ds_format(fmt: str) -> str:
    return {"parquet": "parquet", "csv": "csv", "arrow": "feather",
            "ipc": "feather"}.get(fmt, fmt)
