"""The bytes a statement's scans have to read: the numerator of
``scan_hbm_roofline``.

Rows scanned x logical column widths, each column the statement reads
read once: the same work whatever implements it. Computed from the
statement's ``reads`` (``queries/<name>.json``) and the configuration's
``rows``, ``schema`` and ``logical_widths_bytes``, so a statement added
as a query file needs no figure written anywhere."""


def needed_bytes(query: dict, config: dict) -> int:
    """``query`` is a statement's document, ``config`` a configuration's.
    A table or column the configuration has not raises ``KeyError``."""
    widths = config["logical_widths_bytes"]
    return sum(
        config["rows"][table] * sum(widths[config["schema"][table][column]]
                                    for column in columns)
        for table, columns in query["reads"].items())
