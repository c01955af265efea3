"""Compile in set-up every kernel shape the measured window can use.

The fused scan kernels are jitted on their argument shapes, and two of
those shapes change from serve to serve in a way the traffic cannot pin
down: the member array (the commits above a snapshot's floor; when a view
rescans a dirty min/max lane, every commit it has folded above its floor)
and a view's pending delta buffer (padded to a power of two).  A program
the window meets for the first time would compile inside it; `bench/run.py`
counts the programs built in the window and refuses to report such a run.

`KernelWarmup` records each call the program makes to the jitted kernels
that `repro.kernels.rss_scan_agg.ops` exports: while the mix's own traffic
warms the deployment up, and while `exercise` serves each plan shape the
warm-up asked for, and each registered view's plan, once more through the
fused scan path of a scratch `PagedMirror` that holds no views.  (A view
falls back to that path only when its snapshot gate fails, which a warm-up
may never see.)  It notes the longest member array the traffic passed.
`replay` then calls each recorded kernel again on the arguments it was
recorded with, but for the one that varies: the scan kernels with a member
array of every length up to twice the longest seen, plus four; the delta
fold, for each tile it was seen with, with a buffer of every power of two
from 8 rows to the views' flush size.  The argument that varies is found
by its name in the kernel's signature.  The kernels are pure functions, so
nothing here changes the program's state; the calls only fill JAX's
compile caches.  A recorded call that cannot be replayed raises
`WarmupError`, and the run stops.
"""

from __future__ import annotations

import functools
import inspect

import numpy as np

# jitted kernel -> the name of its argument that varies from serve to serve
SCAN_KERNELS = {"rss_scan_agg": "member_ts",
                "rss_scan_agg_grouped": "member_ts",
                "rss_scan_agg_chunked": "member_ts"}
FOLD_KERNEL, FOLD_ARG, FOLD_TILE = "rss_delta_fold", "delta", "acc"
FOLD_MIN_ROWS = 8          # a view pads its delta buffer to a power of two


class WarmupError(RuntimeError):
    """A recorded kernel call that the warm-up cannot replay."""


def _spec(x, *, static: bool):
    """A hashable stand-in of one argument, as far as JAX's compile cache
    tells calls apart: arrays by type, shape and dtype, static arguments
    (passed by keyword) by value, traced scalars by type."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return (type(x).__name__, tuple(x.shape), str(x.dtype))
    return (type(x).__name__, x if static else None)


def _position(fn, arg: str) -> int:
    return list(inspect.signature(fn).parameters).index(arg)


def _replace(args, kwargs, pos: int, name: str, value):
    """The call's arguments with the one at `pos` (or passed as `name`)
    replaced, passed the way the program passed it."""
    args, kwargs = list(args), dict(kwargs)
    if pos < len(args):
        args[pos] = value
    elif name in kwargs:
        kwargs[name] = value
    else:
        raise WarmupError(f"the call passes no {name!r}")
    return args, kwargs


def _get(args, kwargs, pos: int, name: str):
    return args[pos] if pos < len(args) else kwargs[name]


class KernelWarmup:
    def __init__(self) -> None:
        from repro.kernels.rss_scan_agg import ops
        from repro.tensorstore import materialized
        self.ops = ops
        self.flush_rows = int(materialized.FLUSH_ROWS)
        self.calls: dict = {}          # (name, specs) -> first (args, kwargs)
        self.real: dict = {}
        self.varies: dict = {}         # kernel -> (position, name)
        self.longest = 0               # longest member array recorded
        for name, arg in (*SCAN_KERNELS.items(), (FOLD_KERNEL, FOLD_ARG)):
            fn = getattr(ops, name, None)
            if fn is None:
                raise WarmupError(f"repro.kernels.rss_scan_agg.ops has no "
                                  f"{name}")
            self.real[name] = fn
            self.varies[name] = (_position(fn, arg), arg)
        for name, fn in self.real.items():
            setattr(ops, name, self._recorder(name, fn))

    def _recorder(self, name, fn):
        pos, arg = self.varies[name]

        @functools.wraps(fn)
        def record(*args, **kwargs):
            key = (name,
                   tuple(_spec(a, static=False) for i, a in enumerate(args)
                         if i != pos),
                   tuple(sorted((k, _spec(v, static=True))
                                for k, v in kwargs.items() if k != arg)))
            self.calls.setdefault(key, (args, kwargs))
            if name in SCAN_KERNELS:
                self.longest = max(self.longest,
                                   len(_get(args, kwargs, pos, arg)))
            return fn(*args, **kwargs)
        return record

    def exercise(self, plans, *, slots: int, page_elems: int) -> None:
        """Serve each plan once through the fused scan path of an empty
        scratch mirror, at the shapes the deployment's mirror gives it
        (a plan's sub-store has one page per key, missing keys included)."""
        from repro.tensorstore import PagedMirror
        scratch = PagedMirror(slots=slots, page_elems=page_elems)
        for plan in plans:
            scratch.execute_with_writers(plan, 0, need_writers=False)

    def stop(self) -> None:
        """Give the program its kernels back, unwrapped."""
        for name, fn in self.real.items():
            setattr(self.ops, name, fn)

    def members_to(self) -> int:
        """The longest member array replayed: twice the longest the
        traffic passed, plus four."""
        return 2 * self.longest + 4

    def replay(self) -> int:
        """Call every recorded scan kernel with each member-array length up
        to `members_to()`, and the delta fold with every buffer length a
        view pads to; returns the number of calls made."""
        import jax
        import jax.numpy as jnp

        self.stop()
        calls = []
        fold_tiles = set()
        for (name, _s, _k), (args, kwargs) in self.calls.items():
            pos, arg = self.varies[name]
            if name in SCAN_KERNELS:
                for m in range(self.members_to() + 1):
                    calls.append((name, *_replace(args, kwargs, pos, arg,
                                                  np.zeros(m, np.int32))))
                continue
            tile_pos = _position(self.real[name], FOLD_TILE)
            tile = _get(args, kwargs, tile_pos, FOLD_TILE)
            if tuple(tile.shape) in fold_tiles:
                continue
            fold_tiles.add(tuple(tile.shape))
            rows = FOLD_MIN_ROWS
            while rows <= self.flush_rows:
                calls.append((name, *_replace(
                    args, kwargs, pos, arg,
                    jnp.zeros((rows, tile.shape[1]), jnp.int32))))
                rows *= 2
        self.calls.clear()
        for name, args, kwargs in calls:
            try:
                jax.block_until_ready(self.real[name](*args, **kwargs))
            except Exception as exc:
                raise WarmupError(f"{name} cannot be replayed: "
                                  f"{exc!r:.300}") from exc
        return len(calls)
