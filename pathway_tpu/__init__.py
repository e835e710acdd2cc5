"""pathway_tpu — a TPU-native incremental dataflow framework.

A from-scratch re-design of the Pathway contract (declarative ``Table`` programs over update
streams, executed incrementally) on a JAX/XLA/Pallas substrate: columnar keyed state, batch
deltas per commit, jit'd kernels for dense work, device-mesh sharding for scale-out.

Import as ``import pathway_tpu as pw`` — the namespace mirrors the reference's ``pathway``
package (``python/pathway/__init__.py``).
"""

from __future__ import annotations


def _place_compile_cache() -> None:
    """Give XLA's persistent compilation cache a home before anything compiles.

    A server start compiles ~20 encoder buckets plus a search kernel per
    capacity; on a machine that is thrown away after each run that is most of a
    cold start. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to ``<checkout>/.jax_cache``,
    derived from this package's own path: a directory that moves between runs
    (tempfile, pid, time) never hits. Every entry point (scripts, spawned ranks,
    replica children, the benchmark) passes this line by importing the package.
    """
    import os

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    jax.config.update(
        "jax_compilation_cache_dir", os.path.join(checkout, ".jax_cache")
    )


_place_compile_cache()

# core types
from pathway_tpu.internals import dtype as _dtype_mod
from pathway_tpu.internals.dtype import DType
from pathway_tpu.internals.json import Json
from pathway_tpu.internals.keys import Pointer
from pathway_tpu.internals.schema import (
    ColumnDefinition,
    Schema,
    column_definition,
    schema_builder,
    schema_from_csv,
    schema_from_dict,
    schema_from_pandas,
    schema_from_types,
)
from pathway_tpu.internals.table import Joinable, Table, TableSlice
from pathway_tpu.internals.joins import JoinKind, JoinMode, JoinResult
from pathway_tpu.internals.groupbys import GroupedTable
from pathway_tpu.internals.expression import (
    ColumnExpression,
    ColumnReference,
    apply,
    apply_async,
    apply_with_type,
    cast,
    coalesce,
    declare_type,
    fill_error,
    if_else,
    make_tuple,
    require,
    unwrap,
)
from pathway_tpu.internals.thisclass import left, right, this
from pathway_tpu.internals.reducers import reducers
from pathway_tpu.internals.custom_reducers import BaseCustomAccumulator
from pathway_tpu.internals.parse_graph import G as parse_graph_G
from pathway_tpu.engine.runner import run, run_all
from pathway_tpu.internals import udfs
from pathway_tpu.internals.udfs import (
    UDF,
    AsyncRetryStrategy,
    CacheStrategy,
    DiskCache,
    ExponentialBackoffRetryStrategy,
    FixedDelayRetryStrategy,
    FullyAsyncExecutor,
    InMemoryCache,
    NoRetryStrategy,
    async_executor,
    auto_executor,
    fully_async_executor,
    sync_executor,
    udf,
)
from pathway_tpu.internals.monitoring import MonitoringLevel
from pathway_tpu.internals.iterate import iterate, iteration_limit
from pathway_tpu.internals.row_transformer import (
    ClassArg,
    attribute,
    input_attribute,
    input_method,
    method,
    output_attribute,
    transformer,
)

from pathway_tpu.internals.interactive import LiveTable, enable_interactive_mode
from pathway_tpu.internals.errors import global_error_log, local_error_log

# namespaces
from pathway_tpu import debug, demo, io
from pathway_tpu import persistence
from pathway_tpu.stdlib import graphs, indexing, ml, ordered, statistical, stateful, temporal, viz, utils as _stdlib_utils
from pathway_tpu.stdlib.utils.async_transformer import AsyncTransformer
from pathway_tpu.stdlib.utils.pandas_transformer import pandas_transformer
from pathway_tpu.internals.sql import sql
from pathway_tpu.internals.yaml_loader import load_yaml

# engine alias (parity: ``pathway.engine``)
from pathway_tpu import engine

__version__ = "0.1.0"

Date = _dtype_mod.DATE_TIME_NAIVE
DateTimeNaive = _dtype_mod.DATE_TIME_NAIVE
DateTimeUtc = _dtype_mod.DATE_TIME_UTC
Duration = _dtype_mod.DURATION


def __getattr__(name: str):
    if name == "xpacks":
        import pathway_tpu.xpacks as xpacks

        return xpacks
    raise AttributeError(name)


__all__ = [
    "AsyncTransformer",
    "udfs",
    "BaseCustomAccumulator",
    "CacheStrategy",
    "ColumnDefinition",
    "ColumnExpression",
    "ColumnReference",
    "DType",
    "DiskCache",
    "GroupedTable",
    "InMemoryCache",
    "Joinable",
    "JoinKind",
    "JoinMode",
    "JoinResult",
    "Json",
    "MonitoringLevel",
    "Pointer",
    "Schema",
    "Table",
    "TableSlice",
    "UDF",
    "apply",
    "apply_async",
    "apply_with_type",
    "cast",
    "coalesce",
    "column_definition",
    "debug",
    "declare_type",
    "demo",
    "engine",
    "fill_error",
    "graphs",
    "if_else",
    "indexing",
    "io",
    "iterate",
    "left",
    "load_yaml",
    "make_tuple",
    "ml",
    "ordered",
    "pandas_transformer",
    "persistence",
    "reducers",
    "require",
    "right",
    "run",
    "run_all",
    "schema_builder",
    "schema_from_csv",
    "schema_from_dict",
    "schema_from_pandas",
    "schema_from_types",
    "sql",
    "statistical",
    "stateful",
    "temporal",
    "this",
    "udf",
    "unwrap",
]
