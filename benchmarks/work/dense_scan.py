"""Work of one dense search call, as the configuration's semantics need it:
every row is read once per call and scored against every query of the call.
Counts rows, never padded query buckets or the implementation's temporaries."""


def search_call(n_rows: int, dim: int, queries_per_call: float, row_bytes: int = 4) -> dict:
    return {"bytes": n_rows * dim * row_bytes, "flops": 2.0 * queries_per_call * n_rows * dim}


def query_flops(n_rows: int, dim: int) -> float:
    """Scoring FLOPs one reply needs: its query against every row."""
    return 2.0 * n_rows * dim
