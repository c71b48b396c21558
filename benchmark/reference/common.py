"""Shared by the plain references: exact integer hundredths of the
frames' two-place decimals. The references import nothing of the
program; they get the tables as pandas frames made from the run's seed."""

from __future__ import annotations

import numpy as np


def cents(series) -> np.ndarray:
    """A decimal(…, 2) column (decoded as float) as exact int64 cents."""
    return np.rint(series.to_numpy(dtype=np.float64) * 100.0).astype(np.int64)


#: what sums are accumulated in: the reference's exact int64, or one
#: of the controls' lower precisions
ACCUMS = ("int64", "float32", "bfloat16")


def lower(values: np.ndarray, accum: str) -> np.ndarray:
    """Per-row values as the accumulator's precision takes them in."""
    if accum == "int64":
        return values
    if accum == "float32":
        return values.astype(np.float32)
    if accum == "bfloat16":
        import ml_dtypes  # ships with jax; not the program's

        return values.astype(ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown accumulator {accum!r}; known: {ACCUMS}")


def as_units(x, accum: str):
    """A sum accumulated in ``accum`` as the integer of units it claims:
    the reference's int64 as it is; a control's rounded."""
    if accum == "int64":
        return x
    # pandas may add float32 values in float64: the control's sum is at
    # best the float32 nearest to that, so round the result to float32 too
    return np.rint(np.asarray(x, dtype=np.float64).astype(np.float32)
                   .astype(np.float64)).astype(np.int64)
