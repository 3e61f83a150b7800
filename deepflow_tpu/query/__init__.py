"""Query layer: DF-SQL dialect over the embedded columnar store.

Reference analog: server/querier/engine/clickhouse (SQL dialect -> ClickHouse
SQL). Here the dialect compiles to vectorized numpy execution over
ColumnarTables, with SmartEncoding dictionary translation at the edges.

The names below load on first use: the agent imports ``qtrace`` alone
and never pays for the engine.
"""

import importlib

_LAZY = {"parse": "sql", "execute": "engine", "QueryResult": "engine",
         "build_flame_tree": "flamegraph"}

__all__ = ["parse", "execute", "QueryResult", "build_flame_tree"]


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(f"{__name__}.{mod}"), name)
