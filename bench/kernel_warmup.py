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

JAX compiles a jitted program once per device, and keys it on each array
argument's commitment (placed by `jax.device_put` on a device, or not) and
on the default device in force (`jax.default_device`), even where that is
the device it would use anyway.  So a cell's chips are each warmed, and
each recorded call is replayed in the form the program made it: a call
that ran under a default device is replayed under each chip's; a
committed array argument is put on each chip; an uncommitted one is
copied to the chip uncommitted; host arrays and scalars are passed as
recorded.  A call with no default device and no committed argument runs
on the process's default device whatever the cell's chips, and is
replayed once.  `exercise` serves its scratch mirror in each default-device
setting the recorded calls show.  Calls are told apart by shapes,
commitment and whether a default device was set, never by the device, so
every shape seen on any chip is replayed on all of them: routing decides
at each serve which chip meets which shape.  With more than one chip each
chip's replays run on a thread of their own, so that their compiles
overlap.
"""

from __future__ import annotations

import contextlib
import functools
import inspect
from concurrent.futures import ThreadPoolExecutor

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
    tells calls apart on one device: arrays by type, shape, dtype and
    commitment, static arguments (passed by keyword) by value, traced
    scalars by type."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return (type(x).__name__, tuple(x.shape), str(x.dtype),
                _committed(x))
    return (type(x).__name__, x if static else None)


def _committed(x) -> bool:
    """A JAX array placed on its device by `jax.device_put`."""
    return bool(getattr(x, "committed", False))


def _on_chip(x, chip, moved: dict, *, under: bool):
    """A JAX array argument as the program would have made it on `chip`:
    committed there if it was committed; if not, and the call ran `under`
    a default device, an uncommitted copy made under `chip` as the default
    device; anything else as it is.  `moved` keeps each array's copy, so
    an array that several calls share moves once."""
    import jax
    if not isinstance(x, jax.Array) or x.devices() == {chip} or \
            not (x.committed or under):
        return x
    if id(x) not in moved:
        moved[id(x)] = (jax.device_put(x, chip) if x.committed
                        else jax.device_put(np.asarray(x)))
    return moved[id(x)]


def _like(recorded, value, chip):
    """A replacement of a recorded argument, committed to `chip` where the
    recorded one was committed."""
    if _committed(recorded):
        import jax
        return jax.device_put(value, chip)
    return value


def chip_name(chip) -> str:
    return f"{chip.platform}:{chip.id}"


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
    def __init__(self, chips=None) -> None:
        """`chips`: the cell's devices (default: the first local one)."""
        import jax
        from repro.kernels.rss_scan_agg import ops
        from repro.tensorstore import materialized
        self.ops = ops
        self.chips = list(chips) if chips is not None \
            else jax.local_devices()[:1]
        self.per_chip: dict = {}       # chip name -> calls replayed there
        self.served_under: list = []   # the default devices `exercise` used
        self.flush_rows = int(materialized.FLUSH_ROWS)
        # (name, default device set?, specs) -> first
        # (args, kwargs, default device or None)
        self.calls: dict = {}
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
            import jax
            ctx = jax.config.jax_default_device
            key = (name, ctx is not None,
                   tuple(_spec(a, static=False) for i, a in enumerate(args)
                         if i != pos),
                   tuple(sorted((k, _spec(v, static=True))
                                for k, v in kwargs.items() if k != arg)))
            self.calls.setdefault(key, (args, kwargs, ctx))
            if name in SCAN_KERNELS:
                self.longest = max(self.longest,
                                   len(_get(args, kwargs, pos, arg)))
            return fn(*args, **kwargs)
        return record

    def _settings(self) -> list:
        """The default devices the serve path ran under, as far as the
        calls recorded so far show: None where a call ran with no default
        device set (or where none was recorded), and each chip where one
        ran under a default device."""
        shown = {ctx is not None for _a, _k, ctx in self.calls.values()}
        return ([None] if not shown or False in shown else []) + \
            (self.chips if True in shown else [])

    def exercise(self, plans, *, slots: int, page_elems: int) -> None:
        """Serve each plan once through the fused scan path of an empty
        scratch mirror, at the shapes the deployment's mirror gives it
        (a plan's sub-store has one page per key, missing keys included),
        under each default device of `_settings()`."""
        import jax
        from repro.tensorstore import PagedMirror
        self.served_under = self._settings()
        for chip in self.served_under:
            with (jax.default_device(chip) if chip is not None
                  else contextlib.nullcontext()):
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
        view pads to, on each chip the call can run on (see the module's
        note); returns the number of calls made, and keeps each chip's in
        `per_chip`."""
        self.stop()
        work: dict = {c: [] for c in self.chips}
        for (name, *_key), (args, kwargs, ctx) in self.calls.items():
            placed = ctx is not None or any(
                _committed(v) for v in (*args, *kwargs.values()))
            for chip in self.chips if placed else self.chips[:1]:
                work[chip].append((name, args, kwargs, ctx is not None,
                                   placed))
        self.calls.clear()
        if len(self.chips) == 1:
            counts = [self._replay_on(self.chips[0], work[self.chips[0]])]
        else:
            with ThreadPoolExecutor(len(self.chips)) as pool:
                counts = list(pool.map(
                    lambda c: self._replay_on(c, work[c]), self.chips))
        self.per_chip = {chip_name(c): n for c, n in zip(self.chips, counts)}
        return sum(counts)

    def _replay_on(self, chip, recorded: list) -> int:
        import jax
        n = 0
        fold_tiles: set = set()
        moved: dict = {}
        for name, args, kwargs, under, placed in recorded:
            with (jax.default_device(chip) if under
                  else contextlib.nullcontext()):
                if placed:
                    args = [_on_chip(a, chip, moved, under=under)
                            for a in args]
                    kwargs = {k: _on_chip(v, chip, moved, under=under)
                              for k, v in kwargs.items()}
                for a, kw in self._variants(name, args, kwargs, chip,
                                            fold_tiles):
                    try:
                        jax.block_until_ready(self.real[name](*a, **kw))
                    except Exception as exc:
                        raise WarmupError(f"{name} cannot be replayed: "
                                          f"{exc!r:.300}") from exc
                    n += 1
        return n

    def _variants(self, name, args, kwargs, chip, fold_tiles: set):
        """The recorded call with its varying argument at every length the
        window can pass: member arrays up to `members_to()` long; for each
        tile not yet seen, a delta buffer of every padded length."""
        import jax.numpy as jnp

        pos, arg = self.varies[name]
        recorded = _get(args, kwargs, pos, arg)
        if name in SCAN_KERNELS:
            for m in range(self.members_to() + 1):
                yield _replace(args, kwargs, pos, arg,
                               _like(recorded, np.zeros(m, np.int32), chip))
            return
        tile = _get(args, kwargs, _position(self.real[name], FOLD_TILE),
                    FOLD_TILE)
        if tuple(tile.shape) in fold_tiles:
            return
        fold_tiles.add(tuple(tile.shape))
        rows = FOLD_MIN_ROWS
        while rows <= self.flush_rows:
            yield _replace(args, kwargs, pos, arg, _like(
                recorded, jnp.zeros((rows, tile.shape[1]), jnp.int32), chip))
            rows *= 2
