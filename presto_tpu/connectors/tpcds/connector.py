"""The built-in TPC-DS connector (generated data, never read from disk).

Reference parity: ``presto-tpcds`` (``TpcdsConnectorFactory``,
``TpcdsMetadata``, ``TpcdsSplitManager``, the ``com.teradata.tpcds``
generator) [SURVEY §2.2; reference tree unavailable, paths
reconstructed]. Same split/determinism contract as the TPC-H
connector; additionally produces NULL masks on fact FK columns
(``scan_numpy`` returns ``<col>$valid`` companions).
"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

from presto_tpu.batch import Batch, Dictionary
from presto_tpu.connectors.tpcds import schema as S
from presto_tpu.connectors.tpcds.generator import TpcdsGenerator
from presto_tpu.spi import (
    Split,
    SplitStore,
    narrowed_schema,
    scan_stored,
    split_valids,
)


class TpcdsConnector:
    name = "tpcds"

    DEFAULT_UNITS_PER_SPLIT = 1 << 17

    def __init__(self, sf: float = 1.0, seed: int = 20030115,
                 units_per_split: int | None = None):
        self.sf = sf
        self.gen = TpcdsGenerator(sf, seed)
        self.units_per_split = units_per_split or self.DEFAULT_UNITS_PER_SPLIT
        #: the tables are a pure function of (sf, seed): padded host
        #: columns are kept per split (spi.scan_stored)
        self.scan_store = SplitStore()

    # ---- metadata -------------------------------------------------------
    def tables(self) -> Sequence[str]:
        return list(S.TABLES)

    def schema(self, table: str):
        return S.TABLES[table]

    def dictionaries(self, table: str) -> Mapping[str, Dictionary]:
        return S.table_dicts(table)

    def row_count(self, table: str) -> int:
        return S.row_count(table, self.sf)

    def stats(self, table: str, column: str):
        return S.column_stats(table, column, self.sf)

    def unique_keys(self, table: str):
        return S.UNIQUE_KEYS.get(table, ())

    def func_deps(self, table: str):
        return S.FUNC_DEPS.get(table, {})

    def physical_schema(self, table: str,
                        columns: Sequence[str] | None = None) -> dict:
        """Per-column physical types: the declared stats are join-key
        domains only, so only dictionary-encoded VARCHAR columns narrow
        (their code domain is exactly the dictionary length — int8/int16
        instead of int32 for every low-cardinality dimension string)."""
        cols = list(columns) if columns is not None else list(S.TABLES[table])
        return narrowed_schema(
            {c: S.TABLES[table][c] for c in cols},
            lambda c: None,
            S.table_dicts(table),
        )

    # ---- splits ---------------------------------------------------------
    def splits(self, table: str, target_splits: int = 0) -> Sequence[Split]:
        units = self.gen.base_rows(table)
        per = self.units_per_split
        if target_splits:
            per = max(1, -(-units // target_splits))
        out = []
        for chunk, lo in enumerate(range(0, units, per)):
            hi = min(lo + per, units)
            out.append(Split(table, chunk, lo, hi, hi - lo))
        return out

    # ---- data -----------------------------------------------------------
    def scan_numpy(
        self, split: Split, columns: Sequence[str] | None = None
    ) -> Mapping[str, np.ndarray]:
        return self.gen.generate(split.table, split.chunk, split.lo, split.hi, columns)

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
        return {c: np.concatenate([p[c] for p in parts]) for c in parts[0]}

    def table_pandas(self, table: str, columns: Sequence[str] | None = None):
        """Decoded logical-value DataFrame (NULLs as NaN/None) — the
        oracle's input."""
        import pandas as pd

        from presto_tpu.batch import decode_values

        arrays, valids = split_valids(self.table_numpy(table, columns))
        types = S.TABLES[table]
        dicts = S.table_dicts(table)
        return pd.DataFrame(
            {
                c: decode_values(v, valids.get(c), types[c], dicts.get(c))
                for c, v in arrays.items()
            }
        )
