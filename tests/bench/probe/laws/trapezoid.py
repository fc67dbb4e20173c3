"""Trapezoidal arrivals: a ramp from ``base`` to ``peak`` events/s over
``ramp_s``, ``plateau_s`` at the peak and a ramp back down, repeated, of
``event_size_mb`` events."""
import numpy as np


def program(law: dict, n: int, seed: int) -> list:
    from repro.data.workloads import TrapezoidWorkload

    return [TrapezoidWorkload(peak=float(law["peak"]),
                              ramp_s=float(law["ramp_s"]),
                              plateau_s=float(law["plateau_s"]),
                              base=float(law["base"]),
                              event_size_mb=float(law["event_size_mb"]))
            for _ in range(n)]


def reference(law: dict, t, q):
    base, peak = float(law["base"]), float(law["peak"])
    ramp, plateau = float(law["ramp_s"]), float(law["plateau_s"])
    u = q(np.mod(q(t), 2.0 * ramp + plateau))
    up = q(base + q(q((peak - base) * u) / ramp))
    down = q(peak - q(q((peak - base) * q(u - ramp - plateau)) / ramp))
    rate = np.where(u < ramp, up, np.where(u < ramp + plateau, peak, down))
    return rate, np.full(np.shape(t), float(law["event_size_mb"]))
