"""The built-in TPC-H connector (generated data, never read from disk).

Reference parity: ``presto-tpch`` ``TpchConnectorFactory`` /
``TpchMetadata`` / ``TpchSplitManager`` / ``TpchRecordSetProvider``
[SURVEY §2.2; reference tree unavailable, paths reconstructed]. Splits
are contiguous generation-unit ranges (orders for orders/lineitem, keys
otherwise); data for any split/column subset is deterministic and
order-independent, so the same connector is the scan source, the test
fixture, and the oracle input.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

from presto_tpu.batch import Batch, Dictionary
from presto_tpu.connectors.tpch import schema as S
from presto_tpu.connectors.tpch.generator import TpchGenerator
from presto_tpu.spi import (
    Split,
    SplitStore,
    narrowed_schema,
    scan_stored,
)
from presto_tpu.types import DataType


class TpchConnector:
    name = "tpch"

    #: generation units (orders / keys) per split
    DEFAULT_UNITS_PER_SPLIT = 1 << 17

    def __init__(self, sf: float = 1.0, seed: int = 19920401,
                 units_per_split: int | None = None):
        self.sf = sf
        self.gen = TpchGenerator(sf, seed)
        self.units_per_split = units_per_split or self.DEFAULT_UNITS_PER_SPLIT
        #: the tables are a pure function of (sf, seed): padded host
        #: columns are kept per split (spi.scan_stored)
        self.scan_store = SplitStore()

    # ---- metadata -------------------------------------------------------
    def tables(self) -> Sequence[str]:
        return list(S.TABLES)

    def schema(self, table: str) -> Mapping[str, DataType]:
        return S.TABLES[table]

    def dictionaries(self, table: str) -> Mapping[str, Dictionary]:
        return S.table_dicts(table)

    def row_count(self, table: str) -> int:
        return S.row_count(table, self.sf)

    def stats(self, table: str, column: str):
        return S.column_stats(table, column, self.sf)

    # ---- splits ---------------------------------------------------------
    def splits(self, table: str, target_splits: int = 0) -> Sequence[Split]:
        units = self.gen.base_rows(table)
        per = self.units_per_split
        if target_splits:
            per = max(1, -(-units // target_splits))
        out = []
        chunk = 0
        for lo in range(0, units, per):
            hi = min(lo + per, units)
            hint = (hi - lo) * (7 if table == "lineitem" else 1)
            out.append(Split(table, chunk, lo, hi, hint))
            chunk += 1
        return out

    # ---- data -----------------------------------------------------------
    def scan_numpy(
        self, split: Split, columns: Sequence[str] | None = None
    ) -> Mapping[str, np.ndarray]:
        return self.gen.generate(split.table, split.chunk, split.lo, split.hi, columns)

    def physical_schema(self, table: str,
                        columns: Sequence[str] | None = None) -> dict:
        """Per-column PHYSICAL types for device materialization: the
        generator's exact value domains (column_stats) narrow each
        column to its smallest sufficient signed-int storage — the
        stats-driven narrow-storage lever (ISSUE-5)."""
        cols = list(columns) if columns is not None else list(S.TABLES[table])
        return narrowed_schema(
            {c: S.TABLES[table][c] for c in cols},
            lambda c: self.stats(table, c),
            S.table_dicts(table),
        )

    def scan(
        self,
        split: Split,
        columns: Sequence[str] | None = None,
        capacity: int | None = None,
    ) -> Batch:
        return scan_stored(self, split, columns, capacity)

    # ---- whole-table convenience (tests / oracle) -----------------------
    def table_numpy(self, table: str, columns: Sequence[str] | None = None):
        parts = [self.scan_numpy(s, columns) for s in self.splits(table)]
        return {
            c: np.concatenate([p[c] for p in parts]) for c in parts[0]
        }

    def table_pandas(
        self,
        table: str,
        columns: Sequence[str] | None = None,
        arrays: Mapping[str, np.ndarray] | None = None,
    ):
        """Decoded logical-value DataFrame — the oracle's input.

        ``arrays``: pre-generated columnar arrays for ``table`` (e.g. the
        same ones fed to ``Batch.from_numpy``); when given, generation is
        skipped entirely — the scan input and the oracle input are then
        *literally* the same data, and a full-SF bench run pays for
        generation once instead of twice.
        """
        import pandas as pd

        from presto_tpu.batch import decode_values

        if arrays is None:
            arrays = self.table_numpy(table, columns)
        types = S.TABLES[table]
        dicts = S.table_dicts(table)
        return pd.DataFrame(
            {
                c: decode_values(v, None, types[c], dicts.get(c))
                for c, v in arrays.items()
            }
        )
