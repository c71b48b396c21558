"""presto_tpu — a TPU-native distributed SQL execution framework.

A brand-new engine with the capabilities of the reference
(`sakhuja/presto`, a prestodb/presto fork — see SURVEY.md): columnar
page-at-a-time operators (scan/filter/project, hash aggregation, joins,
sort/topN/window), a SQL frontend with a rule-based distributed planner
that fragments plans at exchange boundaries, and a hash-partitioned
shuffle — rebuilt idiomatically on JAX/XLA:

- struct-of-arrays device ``Batch``es instead of heap ``Page``/``Block``
  objects (reference: presto-common ``com.facebook.presto.common.Page`` /
  ``block/*`` [SURVEY §2.1; reference tree unavailable, paths reconstructed]),
- jit-traced kernels instead of per-query JVM bytecode
  (reference: ``com.facebook.presto.sql.gen.PageFunctionCompiler``),
- ``jax.lax.all_to_all`` over an ICI mesh instead of pull-based HTTP page
  exchanges (reference: ``execution.buffer.*`` + ``operator.ExchangeClient``),
- a single-controller Python driver over ``jax.sharding.Mesh`` instead of
  the coordinator/worker REST protocol (reference: ``execution.scheduler``).

64-bit support is enabled globally: decimals are exact scaled int64 and
aggregate accumulators are 64-bit (TPU emulates s64 with 32-bit pairs;
the hot comparison/hash paths stay 32-bit where values allow).
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent compile cache, placed from OUTSIDE when the environment
# says where (JAX_COMPILATION_CACHE_DIR: JAX reads it itself, nothing
# is set here), else at ONE fixed path under the checkout — the path is
# part of the cache key, so it is never built from a temp name, pid or
# time. A process pinned to the CPU (the tests) gets no directory from
# code: XLA:CPU reloads its own cached AOT results with machine-feature
# error logs, and compile time there is not what the cache is for.
# Most engine steps compile in under JAX's default 1 s / size
# thresholds; the minimums are dropped so they are kept too.
if (not os.environ.get("JAX_COMPILATION_CACHE_DIR")
        and (jax.config.jax_platforms or "").split(",")[0] != "cpu"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

from presto_tpu.types import (  # noqa: E402
    BOOLEAN,
    DATE,
    DOUBLE,
    INTEGER,
    BIGINT,
    DataType,
    TypeKind,
    decimal,
    varchar,
    fixed_bytes,
)
from presto_tpu.batch import Batch, Column, Dictionary  # noqa: E402

__version__ = "0.1.0"

__all__ = [
    "Batch",
    "Column",
    "Dictionary",
    "DataType",
    "TypeKind",
    "BOOLEAN",
    "INTEGER",
    "BIGINT",
    "DOUBLE",
    "DATE",
    "decimal",
    "varchar",
    "fixed_bytes",
]
