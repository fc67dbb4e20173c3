"""Small shared utilities: pytree math, rng streams, padding, timing."""
from __future__ import annotations

import math
import os
import time
from pathlib import Path
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

#: the persistent compile cache's fixed home: ``.jax_cache/`` at the
#: checkout root (a moving path never hits — it is part of the cache key)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point
    (launchers, benchmarks, ``chip_smoke.py``; never at import). Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    is set here; otherwise the cache lives at ``COMPILE_CACHE_DIR``.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def tree_size(tree: PyTree) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def tree_zeros_like(tree: PyTree, dtype=None) -> PyTree:
    return jax.tree.map(lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_scale(a: PyTree, s) -> PyTree:
    return jax.tree.map(lambda x: x * s, a)


def tree_isfinite(tree: PyTree) -> jax.Array:
    leaves = [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(tree)]
    return jnp.stack(leaves).all()


def global_norm(tree: PyTree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


class RngStream:
    """Deterministic named rng stream: stream("attn", layer=3) -> PRNGKey."""

    def __init__(self, seed: int):
        self._root = jax.random.PRNGKey(seed)

    def __call__(self, name: str, **kw) -> jax.Array:
        data = name + "".join(f"|{k}={v}" for k, v in sorted(kw.items()))
        fold = abs(hash(data)) % (2**31 - 1)
        return jax.random.fold_in(self._root, fold)


def round_up(x: int, to: int) -> int:
    return ((x + to - 1) // to) * to


def pad_axis(x: jax.Array, axis: int, target: int) -> jax.Array:
    """Zero-pad `axis` of x up to length `target`."""
    cur = x.shape[axis]
    if cur == target:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - cur)
    return jnp.pad(x, pad)


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024:
            return f"{n:.2f}{unit}"
        n /= 1024
    return f"{n:.2f}PiB"


def human_num(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000:
            return f"{n:.2f}{unit}"
        n /= 1000
    return f"{n:.2f}Q"


class Stopwatch:
    """Wall-clock stopwatch for benchmark harnesses."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def lap(self) -> float:
        now = time.perf_counter()
        dt, self.t0 = now - self.t0, now
        return dt


def timed(fn: Callable, *args, iters: int = 3, warmup: int = 1) -> float:
    """Median wall-clock seconds per call (blocks on jax arrays)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def batched(it: Iterator, n: int):
    buf = []
    for x in it:
        buf.append(x)
        if len(buf) == n:
            yield buf
            buf = []
    if buf:
        yield buf


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Numerically-stable CE. logits (..., V) f32-accumulated, labels (...) int."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - gold


def ema(prev: float, new: float, decay: float) -> float:
    return decay * prev + (1.0 - decay) * new
