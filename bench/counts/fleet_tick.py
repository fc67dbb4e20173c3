"""Work of one fused ``fleet_tick`` window, from the problem's shapes.

T ticks, S latency lanes per tick, N clusters, K-deep streaming p99 head.
The same (T, S, N, K) gives the same count whatever implements the window,
so a roofline share read against it compares implementations fairly.
"""
from __future__ import annotations

import math

F32 = 4
#: rows of the per-cluster constants block (padded to a sublane multiple)
CONSTS_ROWS = 16


def window_arrays(T: int, S: int, N: int, K: int) -> dict:
    """Shapes the window reads and writes, each touched once."""
    return {
        "inputs": [(2, N), (CONSTS_ROWS, N)] + [(T, N)] * 9
                  + [(T, S, N)] * 2,
        "outputs": [(2, N), (7, T, N), (5, T, N), (K, N)],
    }


def window_bytes(T: int, S: int, N: int, K: int) -> float:
    arrs = window_arrays(T, S, N, K)
    return float(F32 * sum(math.prod(s) for s in
                           arrs["inputs"] + arrs["outputs"]))


def window_ops(T: int, S: int, N: int, K: int) -> float:
    """Per tick and cluster: the ascending bitonic sort of the S lanes
    (S/2 · log2 S · (log2 S + 1)/2 compare-exchanges, 2 ops each), the
    bitonic merge of the K-deep head with them ((S+K)/2 · log2(S+K)
    compare-exchanges), the lane latency build (~4 ops a lane) and the
    ~40-op tick recurrence."""
    lg = math.log2(S)
    sort_ce = S / 2 * lg * (lg + 1) / 2
    merge_ce = (S + K) / 2 * math.log2(S + K)
    return float(T * N * (2 * (sort_ce + merge_ce) + 4 * S + 40))


def roofline_seconds(T, S, N, K, peaks: dict) -> tuple[float, str]:
    """(least seconds one window can take on the chip, the bound)."""
    t_ops = window_ops(T, S, N, K) / peaks["flops_per_s"]
    t_bytes = window_bytes(T, S, N, K) / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
