"""Roofline share of the Mosaic ``fleet_tick`` window kernel
(``kernels/fleet_tick.py`` ``_tick_window_kernel``): the least time the chip
could take for the kernel calls in the traced window (the larger of their
operations over peak FLOP/s and their bytes over peak HBM bytes/s, from
``counts/fleet_tick.py`` at the traffic's (T, S, N, K)) over the kernel's
summed device time."""
import importlib.util
import re

import trace_reduce

#: the kernel's op in the device trace: the Mosaic custom call is named
#: after the jitted ``fleet_tick_window`` that wraps the ``pallas_call``
KERNEL = re.compile(r"^%?fleet_tick_window(\.\d+)?$")


def _counts(bench):
    spec = importlib.util.spec_from_file_location(
        "counts_fleet_tick", bench / "counts" / "fleet_tick.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(ctx):
    shape = ctx.traffic.get("window_kernel")
    win = ctx.view.window()
    if not shape or win is None or not ctx.view.ops:
        return None
    evs = [e for e in ctx.view.ops[min(ctx.view.ops)]
           if KERNEL.match(trace_reduce.op_name(e.name))
           and win[0] <= e.start < win[1]]
    secs = sum(e.dur for e in evs) / 1e9
    if not evs or secs <= 0:
        return None
    peaks = ctx.peaks[ctx.device_kind]
    least, _ = _counts(ctx.bench).roofline_seconds(
        shape["T"], shape["S"], int(ctx.traffic["fleet"]), shape["K"], peaks)
    return 100.0 * least * len(evs) / secs
