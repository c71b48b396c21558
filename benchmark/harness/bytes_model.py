"""The bytes a scan kernel must read: rows times the storage bytes of
the columns it reads, from the connector's physical schema. Kept with
the benchmark so that no PR that claims a gain can change it."""

from __future__ import annotations

import numpy as np


def column_bytes(conn, table: str, columns) -> int:
    """Storage bytes per row of ``columns`` (the narrowed physical
    types the scan uploads; a type without one is its logical width)."""
    total = 0
    for name, dtype in conn.physical_schema(table, list(columns)).items():
        phys = getattr(dtype, "phys", None)
        if phys:
            total += np.dtype(phys).itemsize
        else:
            total += int(getattr(dtype, "width", 0) or 8)
    return total


def template_scan_bytes(conn, template: dict) -> int:
    """Bytes one execution of ``template`` must read from its scanned
    tables: nominal rows x bytes of the columns its reference reads
    (the query reads the same columns)."""
    return sum(conn.row_count(table) * column_bytes(conn, table, cols)
               for table, cols in template["reads"].items()
               if table in template["scans"])
