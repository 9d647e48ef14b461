"""Primitive registry — centralised backend dispatch with cached jitted
kernels and a per-primitive tuning table.

This is the JAX rendition of the paper's single-call-site claim: in AK.jl,
``mapreduce(f, op, itr)`` picks the specialised method via Julia multiple
dispatch.  Here every AK primitive is registered ONCE as a :class:`Primitive`
record carrying

  * its portable (``jnp``) implementation,
  * its Pallas TPU implementation (``None`` when the portable one already is
    the right shape for every backend, e.g. ``bincount``'s segment-sum),
  * which call options are static (select a trace) vs traced operands,
  * tunable defaults drawn from the central, overridable
    :class:`TuningTable` — AK's ``switch_below`` host-finish trade-off
    generalised, plus block geometry and Pallas interpret mode.

``Primitive.__call__`` then does the whole dispatch dance in one place:

  1. resolve the backend policy via :mod:`repro.core.dispatch`
     (auto / jnp / pallas, scoped overrides respected);
  2. demote pallas→jnp below the primitive's ``switch_below`` element count
     (the paper's "stop paying launch overhead on tiny tails" knob, now a
     declarative table entry instead of hard-coded branches);
  3. look up a **cached** jitted kernel keyed on
     (backend, static opts, tuning) — instead of rebuilding
     ``jax.jit(functools.partial(...))`` on every call, which is what made
     hot loops (the serve-loop sampler, MoE routing) retrace continuously;
  4. record instrumentation counters (calls, cache hits, traces) queryable
     for benchmarks (``benchmarks/dispatch_overhead.py``).

Registered implementations use the normalised signature
``impl(*operands, **static_opts)``: positional arguments are traced arrays,
keyword arguments (functions ``f``/``op``, dtypes, flags, scalar units) are
static and become part of the cache key.  Static values that cannot be
hashed (e.g. tracers flowing in from an outer trace) fall back to an
uncached direct call — correct, just not cached, exactly like closing over
them did before.

Adding a backend (e.g. a GPU-tiled path) is now one registration point
instead of an edit in every wrapper module.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import types
from collections import OrderedDict
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch
from repro.kernels import common as KC
from repro.kernels import hist_kernel, map_kernel, reduce_kernel, scan_kernel
from repro.kernels import merge_kernel, nucleus_kernel, search_kernel
from repro.kernels import page_kernel, segment_kernel, sort_kernel
from repro.kernels import ref as kref
from repro.runtime import metrics, telemetry


# --------------------------------------------------------------------------
# Tuning table
# --------------------------------------------------------------------------

#: Tunables every primitive understands. ``switch_below``: element count
#: under which a pallas request is demoted to the portable path (0 = never).
#: ``interpret``: force Pallas interpret mode on/off (None = auto: interpret
#: everywhere except real TPUs). ``block_rows``/``block_cols``: kernel tile
#: geometry (None = the (8, 1024) default in kernels/common.py).
#: ``sort_hyper``: the bitonic network's hyper-block order m — each cross
#: launch fuses up to m stages over 2^m blocks in VMEM (None = the kernel's
#: default, 0 = the unfused one-launch-per-stage baseline; sort family only).
#: ``page_size``: tokens per KV-cache page (None = the primitive's own
#: default; power of two so page/offset splits are shifts; page_gather and
#: the paged serving engine only).
TUNABLE_KEYS = (
    "switch_below", "interpret", "block_rows", "block_cols", "sort_hyper",
    "page_size",
)

#: What the streaming (map/reduce/scan/hist/search) kernels honour — all the
#: common knobs except the sort network's hyper order.
STREAM_TUNABLES = ("switch_below", "interpret", "block_rows", "block_cols")

_COMMON_DEFAULTS = {
    "switch_below": 0,
    "interpret": None,
    "block_rows": None,
    "block_cols": None,
    "sort_hyper": None,
    "page_size": None,
}

#: Primitives built on the bitonic network: their block must stay a power of
#: two (the network's wiring is the binary representation of the index), so
#: block_rows gets the extra pow2 check on top of the sublane multiple.
_SORT_FAMILY = (
    "sort", "sort_kv", "argsort", "sort_batched", "argsort_batched", "topk",
    "merge", "merge_kv", "nucleus_mask", "segmented_sort",
)


def _validate_tuning(name: str, kv: dict, allowed=TUNABLE_KEYS) -> None:
    for k, v in kv.items():
        if k not in TUNABLE_KEYS:
            raise KeyError(
                f"unknown tunable {k!r} for primitive {name!r}; "
                f"valid keys: {TUNABLE_KEYS}"
            )
        if k not in allowed:
            # e.g. sort_hyper for a streaming kernel or any knob for
            # bincount (no pallas impl): rejecting loudly beats a silent
            # no-op the user believes took effect
            raise KeyError(
                f"primitive {name!r} does not support tunable {k!r} "
                f"(its kernels ignore it); supported: {tuple(allowed)}"
            )
        if k == "switch_below" and (not isinstance(v, int) or v < 0):
            raise ValueError(f"switch_below must be a non-negative int, got {v!r}")
        if k == "interpret" and not (v is None or isinstance(v, bool)):
            # bool('false') is True — reject strings loudly rather than
            # silently forcing interpret mode on a real TPU
            raise ValueError(f"interpret must be True/False/None, got {v!r}")
        if k == "block_rows" and v is not None and (v <= 0 or v % KC.SUBLANES):
            raise ValueError(f"block_rows must be a multiple of {KC.SUBLANES}")
        if (
            k == "block_rows" and v is not None and name in _SORT_FAMILY
            and v & (v - 1)
        ):
            raise ValueError(
                f"{name!r} needs a power-of-two block_rows (bitonic network "
                f"wiring), got {v!r}"
            )
        if k == "block_cols" and v is not None and (
            v < KC.LANES or v & (v - 1) or v % KC.LANES
        ):
            raise ValueError(
                f"block_cols must be a power-of-two multiple of {KC.LANES}"
            )
        if k == "sort_hyper" and not (
            v is None or (isinstance(v, int) and not isinstance(v, bool)
                          and 0 <= v <= 6)
        ):
            # 2^6 blocks × 8 Ki elements = 2 MiB f32 keys per grid step —
            # past that the hyper-block stops fitting VMEM alongside values
            # and double buffering
            raise ValueError(
                f"sort_hyper must be None or an int in [0, 6], got {v!r}"
            )
        if k == "page_size" and not (
            v is None or (isinstance(v, int) and not isinstance(v, bool)
                          and 1 <= v <= 1024 and not (v & (v - 1)))
        ):
            # pow2 keeps (page, offset) splits cheap; 1024 tokens/page is
            # already a whole contiguous cache row at serving scale
            raise ValueError(
                f"page_size must be None or a power-of-two int in "
                f"[1, 1024], got {v!r}"
            )


class TuningTable:
    """Central per-primitive performance knobs.

    Precedence, weakest first (DESIGN.md §7): registered defaults < active
    named **presets** (``preset()`` scopes — a caller's hand-rolled profile,
    e.g. the serve sampler) < the attached **autotune cache** (measured per
    (primitive, dtype, size-class); ``resolve()`` only) < global ``set()``
    < scoped ``overrides()`` (innermost wins). Explicit always beats
    measured, measured beats hand-rolled. All scoped state — ``preset()``,
    ``overrides()``, ``using_cache()`` — is thread-local, so concurrent
    serve loops can tune independently; ``set()`` and ``attach_cache()``
    are deliberate process-global installs."""

    def __init__(self):
        self._defaults: dict[str, dict] = {}
        self._allowed: dict[str, tuple] = {}
        self._global: dict[str, dict] = {}
        self._presets: dict[str, dict[str, dict]] = {}
        #: attached autotune cache (duck-typed: ``.lookup(name, dtype,
        #: size_class)`` — see repro.tune.cache.TuneCache). None = off.
        self._autotune = None
        self._tls = threading.local()

    def _register(self, name: str, defaults: dict | None, allowed) -> None:
        merged = dict(_COMMON_DEFAULTS)
        if defaults:
            _validate_tuning(name, defaults, allowed)
            merged.update(defaults)
        self._defaults[name] = merged
        self._allowed[name] = tuple(allowed)

    def _stack(self) -> list:
        if not hasattr(self._tls, "stack"):
            self._tls.stack = []
        return self._tls.stack

    def _check_name(self, name: str) -> None:
        if name not in self._defaults:
            raise KeyError(
                f"unknown primitive {name!r}; registered: "
                f"{sorted(self._defaults)}"
            )

    def _preset_stack(self) -> list:
        if not hasattr(self._tls, "presets"):
            self._tls.presets = []
        return self._tls.presets

    def lookup(self, name: str) -> dict:
        """Size-agnostic knob resolution — ``resolve`` minus the cache
        layer (no size, no cache key). One merge implementation for both."""
        return self.resolve(name)[0]

    def resolve(self, name: str, *, n: int | None = None,
                dtype=None) -> tuple[dict, str | None]:
        """Size/dtype-aware knob resolution — ``lookup`` plus the attached
        autotune cache, consulted at the measured layer (above presets,
        below explicit ``set``/``overrides``).

        Returns ``(knobs, backend_hint)``: ``backend_hint`` is the cache's
        measured-best backend for this (primitive, dtype, size-class) key,
        or ``None`` when no cache is attached / the key misses / the entry
        carries no verdict. ``Primitive.__call__`` honours the hint only
        when the caller's policy is ``auto`` — an explicit backend, a
        scoped ``dispatch.backend(...)`` or a ``switch_below`` override
        still wins."""
        self._check_name(name)
        out = dict(self._defaults[name])
        for mapping in self._preset_stack():
            out.update(mapping.get(name, {}))
        hint = None
        cache = self._active_cache()
        if cache is not None and n:
            entry = cache.lookup(
                name, str(dtype), KC.size_class(int(n))
            )
            if entry:
                allowed = self._allowed[name]
                knobs = {
                    k: v for k, v in (entry.get("knobs") or {}).items()
                    if k in allowed
                }
                try:
                    _validate_tuning(name, knobs, allowed)
                except (KeyError, ValueError):
                    knobs = {}  # hand-edited/corrupt entry: defaults win
                out.update(knobs)
                if entry.get("backend") in ("jnp", "pallas"):
                    hint = entry["backend"]
        out.update(self._global.get(name, {}))
        for layer in self._stack():
            out.update(layer.get(name, {}))
        return out, hint

    def set(self, name: str, **kv) -> None:
        """Globally override tunables for one primitive."""
        self._check_name(name)
        _validate_tuning(name, kv, self._allowed[name])
        self._global.setdefault(name, {}).update(kv)

    def reset(self, name: str | None = None) -> None:
        if name is None:
            self._global.clear()
        else:
            # a typo ("sortt") must not silently reset nothing
            self._check_name(name)
            self._global.pop(name, None)

    # -- named presets (hand-rolled caller profiles) -----------------------
    def register_preset(self, preset: str, mapping: dict[str, dict]) -> dict:
        """Register a named knob profile ({primitive: {tunable: value}}),
        validated now, applied via ``preset(name)`` scopes. Presets sit
        BELOW the autotune cache: a measured knob set overrides the
        hand-rolled profile, and ``repro.tune`` seeds the cache from them
        so un-measured keys keep the caller's numbers. Returns a READ-ONLY
        view of the validated snapshot (what ``preset()`` applies):
        mutating the exported profile raises instead of silently diverging
        from the live preset — re-register to change it."""
        checked = {}
        for name, kv in mapping.items():
            self._check_name(name)
            _validate_tuning(name, kv, self._allowed[name])
            checked[name] = dict(kv)
        self._presets[preset] = checked
        return types.MappingProxyType(
            {k: types.MappingProxyType(v) for k, v in checked.items()}
        )

    def preset_names(self) -> tuple:
        return tuple(sorted(self._presets))

    def preset_mapping(self, preset: str) -> dict[str, dict]:
        try:
            return {k: dict(v) for k, v in self._presets[preset].items()}
        except KeyError:
            raise KeyError(
                f"unknown preset {preset!r}; registered: "
                f"{sorted(self._presets)}"
            ) from None

    @contextlib.contextmanager
    def preset(self, preset: str):
        """Scoped activation of a registered preset (weakest layer above
        the registered defaults)."""
        mapping = self._presets.get(preset)
        if mapping is None:
            raise KeyError(
                f"unknown preset {preset!r}; registered: "
                f"{sorted(self._presets)}"
            )
        self._preset_stack().append(mapping)
        try:
            yield self
        finally:
            self._preset_stack().pop()

    # -- autotune cache attachment -----------------------------------------
    def _cache_stack(self) -> list:
        if not hasattr(self._tls, "caches"):
            self._tls.caches = []
        return self._tls.caches

    def _active_cache(self):
        stack = self._cache_stack()
        return stack[-1] if stack else self._autotune

    @property
    def autotune(self):
        return self._active_cache()

    def attach_cache(self, cache) -> None:
        """Process-global install (``None`` detaches) of an autotune cache;
        consulted by ``resolve()`` for every registry call until detached.
        Thread-scoped ``using_cache()`` attachments shadow it."""
        self._autotune = cache

    @contextlib.contextmanager
    def using_cache(self, cache):
        """Scoped, THREAD-LOCAL cache attachment: ``with
        tuning.using_cache(c): ...``. Inside the scope this thread resolves
        against ``cache`` (``None`` = explicitly no cache), shadowing any
        global ``attach_cache`` install; other threads are untouched."""
        self._cache_stack().append(cache)
        try:
            yield cache
        finally:
            self._cache_stack().pop()

    @contextlib.contextmanager
    def overrides(self, mapping: dict[str, dict] | None = None, **per_prim):
        """Scoped overrides: ``with tuning.overrides({"mapreduce":
        {"switch_below": 4096}}): ...`` (or primitive-name kwargs)."""
        layer: dict[str, dict] = {}
        for src in (mapping or {}), per_prim:
            for name, kv in src.items():
                self._check_name(name)
                _validate_tuning(name, kv, self._allowed[name])
                layer.setdefault(name, {}).update(kv)
        self._stack().append(layer)
        try:
            yield self
        finally:
            self._stack().pop()


tuning = TuningTable()


# --------------------------------------------------------------------------
# Primitive records
# --------------------------------------------------------------------------

@dataclasses.dataclass
class PrimitiveStats:
    """Instrumentation counters: ``calls`` (every __call__), ``cache_hits``
    (served an already-built jitted kernel), ``traces`` (actual jax traces —
    flat counters across repeated same-shape calls prove the retrace
    elimination), ``uncached`` (unhashable statics → direct call)."""

    calls: int = 0
    cache_hits: int = 0
    traces: int = 0
    uncached: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class _Unhashable:
    pass


_UNHASHABLE = _Unhashable()


def _static_key(v: Any):
    """Hashable cache-key form of a static option, or _UNHASHABLE.

    Tracers AND concrete jax Arrays are both uncacheable: a tracer must
    never be baked into a cached closure, and reading a device scalar's
    value (``init=x.max()``) would block on the in-flight computation every
    call and mint a fresh cache key per distinct value — per-value retrace
    churn on exactly the hot paths the cache exists for. Host values
    (Python scalars, 0-d numpy) key by value for free.
    """
    if isinstance(v, (jax.core.Tracer, jax.Array)):
        return _UNHASHABLE
    try:
        hash(v)
        return v
    except TypeError:
        pass
    if isinstance(v, np.ndarray) and v.ndim == 0:
        return ("scalar", str(v.dtype), v.item())
    return _UNHASHABLE


class Primitive:
    """One registered AK primitive: both impls + static spec + tunables."""

    def __init__(
        self,
        name: str,
        jnp_impl: Callable,
        pallas_impl: Callable | None = None,
        *,
        tunables: tuple = STREAM_TUNABLES,
        tuning_defaults: dict | None = None,
        switch_measure: str = "size",
        doc: str = "",
        cache_size: int = 256,
    ):
        self.name = name
        self.jnp_impl = jnp_impl
        self.pallas_impl = pallas_impl
        # what switch_below compares against: "size" (total elements) for
        # 1-D primitives, "last_axis" for the batched sort family — there
        # the per-ROW length decides whether the network beats the portable
        # path (a (512, 8) router top-k is 4096 elements but 8-wide rows)
        if switch_measure not in ("size", "last_axis"):
            raise ValueError(f"bad switch_measure {switch_measure!r}")
        self.switch_measure = switch_measure
        self.doc = doc
        # which table knobs this primitive's kernels actually honour —
        # the table rejects overrides outside this set
        self.tunables = tuple(tunables) if pallas_impl is not None else ()
        self.stats = PrimitiveStats()
        self._cache: OrderedDict[tuple, Callable] = OrderedDict()
        self._cache_lock = threading.Lock()
        self._cache_size = cache_size
        # validated here, installed into the table by register() — a record
        # that fails registration must not touch the live tuning table
        if tuning_defaults:
            _validate_tuning(name, tuning_defaults, self.tunables)
        self._tuning_defaults = tuning_defaults

    # -- backend selection -------------------------------------------------
    def _impl(self, backend: str) -> Callable:
        if backend == "pallas" and self.pallas_impl is not None:
            return self.pallas_impl
        return self.jnp_impl

    def _switch_size(self, operands) -> int:
        """What ``switch_below`` (and the autotune size-class) compares:
        total elements, or the last-axis length for batched primitives.
        Non-array first operands (host scalars) count as size 0 — nothing
        to tile, and no size class to resolve against."""
        x = operands[0] if operands else None
        n = getattr(x, "size", 0) if x is not None else 0
        if n and self.switch_measure == "last_axis" and getattr(
            x, "ndim", 0
        ):
            n = x.shape[-1]
        return n

    def _select_backend(self, backend, n: int, switch_below: int,
                        hint: str | None = None) -> str:
        policy = backend or dispatch.default_backend()
        if policy == "auto" and hint is not None \
                and self.pallas_impl is not None:
            # measured crossover from the attached autotune cache: under an
            # "auto" policy the cache's per-size-class verdict replaces the
            # platform default (it was measured on THIS device fingerprint).
            # Explicit backends and scoped dispatch.backend() still win.
            resolved = hint
        else:
            resolved = dispatch.resolve(backend)
        if resolved != "pallas":
            return resolved
        if self.pallas_impl is None:
            return "jnp"
        # AK's host-finish trade-off: tiny inputs skip the tiled kernel
        # (and empty ones always do — nothing to tile).
        if n == 0 or n < switch_below:
            return "jnp"
        return "pallas"

    # -- the single call site ---------------------------------------------
    def __call__(self, *operands, backend: str | None = None, **opts):
        with self._cache_lock:  # counters are read-modify-write
            self.stats.calls += 1
        x = operands[0] if operands else None
        n = self._switch_size(operands)
        tune, hint = tuning.resolve(
            self.name, n=n, dtype=getattr(x, "dtype", None)
        )
        switch_below = opts.pop("switch_below", None)
        if switch_below is None:
            switch_below = tune["switch_below"]
        resolved = self._select_backend(backend, n, switch_below, hint)

        # Telemetry span per dispatch (DESIGN.md §11); with telemetry off
        # and no profile recording, no span is built at all.
        if not telemetry.active():
            return self._dispatch(operands, opts, resolved, tune)
        with telemetry.span("ak." + self.name, cat="primitive",
                            backend=resolved, n=int(n)):
            return self._dispatch(operands, opts, resolved, tune)

    def _dispatch(self, operands, opts, resolved: str, tune: dict):
        # interpret/block geometry only reach Pallas kernels; keying the
        # jnp path on them would compile duplicate identical executables
        # whenever a geometry override is active.
        if resolved == "pallas":
            tune_key = (
                tune["interpret"], tune["block_rows"], tune["block_cols"],
                tune["sort_hyper"],
            )
            scope = dict(
                interpret=tune["interpret"],
                block_rows=tune["block_rows"],
                block_cols=tune["block_cols"],
                sort_hyper=tune["sort_hyper"],
            )
        else:
            tune_key = None
            scope = {}
        statics = []
        for k in sorted(opts):
            h = _static_key(opts[k])
            if h is _UNHASHABLE:
                statics = None
                break
            statics.append((k, h))

        if statics is None:
            # Unhashable static (tracer init etc.): direct call, no cache.
            with self._cache_lock:
                self.stats.uncached += 1
            with KC.launch_attribution(self.name), KC.tuning_scope(**scope):
                return self._impl(resolved)(*operands, **opts)

        key = (resolved, tuple(statics), tune_key)
        with self._cache_lock:
            fn = self._cache.get(key)
            if fn is not None:
                self.stats.cache_hits += 1
                self._cache.move_to_end(key)
        if fn is not None:
            return fn(*operands)

        impl, frozen_opts = self._impl(resolved), dict(opts)
        prim, lock = self, self._cache_lock

        def traced(*arrays):
            # Runs only when jax (re)traces: an exact trace counter.
            # ``prim.stats`` (not a captured object) so reset_stats() also
            # covers retraces of already-cached kernels. Launch attribution
            # lives HERE (not in __call__) because launches happen at trace
            # time — including retraces of cached kernels on new shapes.
            with lock:
                prim.stats.traces += 1
            with KC.launch_attribution(prim.name), KC.tuning_scope(**scope):
                return impl(*arrays, **frozen_opts)

        fn = jax.jit(traced)
        # NOTE: a fresh closure passed as a static (``f=lambda ...`` built
        # per call) gets a fresh identity and therefore a fresh entry each
        # call — exactly like handing jax.jit a new function object. The
        # LRU bounds the damage to ``cache_size`` retained executables per
        # primitive; hot callers should hoist their closures (see
        # core/ops.py::_identity).
        with self._cache_lock:
            self._cache[key] = fn
            while len(self._cache) > self._cache_size:
                self._cache.popitem(last=False)
        return fn(*operands)

    # -- introspection -----------------------------------------------------
    def cache_keys(self) -> tuple:
        return tuple(self._cache)

    def cache_backends(self) -> tuple:
        """Backends with at least one cached kernel (test observability)."""
        return tuple(sorted({k[0] for k in self._cache}))

    def clear(self) -> None:
        with self._cache_lock:
            self._cache.clear()

    def reset_stats(self) -> None:
        with self._cache_lock:
            self.stats = PrimitiveStats()


# --------------------------------------------------------------------------
# Registry surface
# --------------------------------------------------------------------------

_REGISTRY: dict[str, Primitive] = {}


def register(prim: Primitive) -> Primitive:
    if prim.name in _REGISTRY:
        raise ValueError(f"primitive {prim.name!r} already registered")
    _REGISTRY[prim.name] = prim
    tuning._register(prim.name, prim._tuning_defaults, prim.tunables)
    return prim


def get(name: str) -> Primitive:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown primitive {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def call(name: str, *operands, **kw):
    return get(name)(*operands, **kw)


def names() -> tuple:
    return tuple(sorted(_REGISTRY))


def stats(name: str | None = None) -> dict:
    if name is not None:
        return get(name).stats.as_dict()
    return {n: p.stats.as_dict() for n, p in sorted(_REGISTRY.items())}


def reset_stats() -> None:
    for p in _REGISTRY.values():
        p.reset_stats()


def clear_caches() -> None:
    for p in _REGISTRY.values():
        p.clear()


def _metrics_collector(reg) -> None:
    """Pull-sync the legacy PrimitiveStats + launch tallies into the
    process metrics registry at snapshot time (runtime/metrics.py).
    ``registry.stats()`` and ``KC.launch_count()`` stay the source of
    truth; ``ak.telemetry.snapshot()`` always agrees with them."""
    calls = reg.counter("ak_registry_calls_total",
                        "Primitive.__call__ dispatches")
    hits = reg.counter("ak_registry_cache_hits_total",
                       "dispatches served by a cached jitted kernel")
    traces = reg.counter("ak_registry_traces_total",
                         "jax (re)traces of registered impls")
    uncached = reg.counter("ak_registry_uncached_total",
                           "uncacheable direct calls (unhashable statics)")
    for name, p in _REGISTRY.items():
        s = p.stats
        calls.set_total(s.calls, primitive=name)
        hits.set_total(s.cache_hits, primitive=name)
        traces.set_total(s.traces, primitive=name)
        uncached.set_total(s.uncached, primitive=name)
    launches = reg.counter("ak_pallas_launches_total",
                           "trace-time pallas_call launches")
    for label, n in KC.launch_counts().items():
        launches.set_total(n, primitive=label)


metrics.register_collector(_metrics_collector)


# --------------------------------------------------------------------------
# Registrations — THE one place each primitive's two implementations and
# tuned defaults live. core/*.py and kernels/ops.py delegate here.
# --------------------------------------------------------------------------

def _astype(x, out_dtype):
    return x.astype(out_dtype) if out_dtype is not None else x


def _jnp_map(*arrays, f, out_dtype=None):
    return _astype(kref.map_ref(f, *arrays), out_dtype)


def _pallas_map(*arrays, f, out_dtype=None):
    return map_kernel.map_blocks(f, *arrays, out_dtype=out_dtype)


def _jnp_mapreduce(*arrays, f, op, init, out_dtype=None):
    return kref.reduce_ref(f, op, *arrays, unit=init, out_dtype=out_dtype)


def _pallas_mapreduce(*arrays, f, op, init, out_dtype=None):
    return reduce_kernel.reduce_blocks(
        f, op, *arrays, unit=init, out_dtype=out_dtype
    )


def _jnp_accumulate(x, *, op, init, inclusive=True):
    return kref.scan_ref(op, x, unit=init, exclusive=not inclusive)


def _pallas_accumulate(x, *, op, init, inclusive=True):
    return scan_kernel.scan_blocks(op, x, unit=init, exclusive=not inclusive)


def _pallas_argsort(keys):
    idx = jnp.arange(keys.shape[0], dtype=jnp.int32)
    _, perm = sort_kernel.bitonic_sort_kv(keys, idx, tie_break=True)
    return perm


def _jnp_minmax_histogram(x, lo, hi, *, nbins):
    return kref.minmax_histogram_ref(x, nbins, lo, hi)


def _pallas_minmax_histogram(x, lo, hi, *, nbins):
    return hist_kernel.minmax_histogram_blocks(x, nbins, lo, hi)


def _bincount_impl(ids, *, nbins):
    # Linear-memory segment-sum (scatter-add under the hood — XLA's
    # deterministic sorted-scatter on TPU), replacing the O(n·nbins)
    # one-hot contraction. Out-of-range ids land in a ghost segment and
    # are dropped, matching the one-hot semantics exactly.
    flat = ids.reshape(-1)
    valid = (flat >= 0) & (flat < nbins)
    seg = jnp.where(valid, flat, nbins)
    counts = jax.ops.segment_sum(
        jnp.ones_like(seg, dtype=jnp.int32), seg, num_segments=nbins + 1
    )
    return counts[:nbins]


map_p = register(Primitive(
    "map", _jnp_map, _pallas_map,
    doc="foreachindex/map_elements: tiled elementwise f over arrays",
))

mapreduce_p = register(Primitive(
    "mapreduce", _jnp_mapreduce, _pallas_mapreduce,
    doc="mapreduce(f, op, arrays; init) -> scalar",
))

accumulate_p = register(Primitive(
    "accumulate", _jnp_accumulate, _pallas_accumulate,
    doc="prefix scan (inclusive/exclusive), single pass",
))

# The sort family honours the streaming knobs plus ``sort_hyper``: block
# geometry re-tiles the network (power-of-two blocks only — validated
# above) and ``sort_hyper`` picks how many cross stages each hyper-block
# launch fuses in VMEM (kernels/sort_kernel.py; DESIGN.md §2a). NOT the
# full TUNABLE_KEYS: ``page_size`` belongs to the paged-cache gather only.
_SORT_TUNABLES = STREAM_TUNABLES + ("sort_hyper",)

sort_p = register(Primitive(
    "sort",
    lambda x, *, descending=False: kref.sort_ref(x, descending=descending),
    lambda x, *, descending=False: sort_kernel.bitonic_sort(
        x, descending=descending
    ),
    tunables=_SORT_TUNABLES,
    doc="1-D sort (AK merge_sort; bitonic network on TPU)",
))

sort_kv_p = register(Primitive(
    "sort_kv",
    lambda k, v, *, tie_break=False: kref.sort_kv_ref(
        k, v, tie_break=tie_break
    ),
    lambda k, v, *, tie_break=False: sort_kernel.bitonic_sort_kv(
        k, v, tie_break=tie_break
    ),
    tunables=_SORT_TUNABLES,
    doc="key/value pair sort (AK merge_sort_by_key)",
))

argsort_p = register(Primitive(
    "argsort", kref.argsort_ref, _pallas_argsort,
    tunables=_SORT_TUNABLES,
    doc="stable index permutation (AK sortperm)",
))


def _jnp_sort_batched(x, *, descending=False):
    s = jnp.sort(x, axis=-1)
    return s[..., ::-1] if descending else s


def _jnp_argsort_batched(x):
    return jnp.argsort(x, axis=-1, stable=True).astype(jnp.int32)


def _jnp_topk(x, *, k):
    return jax.lax.top_k(x, k)


def _pallas_topk(x, *, k):
    # Sort-derived top-k with lax.top_k's exact tie order — see
    # bitonic_topk_batched for why it avoids key negation (INT_MIN wraps).
    return sort_kernel.bitonic_topk_batched(x, k)


sort_batched_p = register(Primitive(
    "sort_batched", _jnp_sort_batched,
    lambda x, *, descending=False: sort_kernel.bitonic_sort_batched(
        x, descending=descending
    ),
    tunables=_SORT_TUNABLES, switch_measure="last_axis",
    doc="last-axis sort of (..., n) — the vmapped bitonic network",
))

argsort_batched_p = register(Primitive(
    "argsort_batched", _jnp_argsort_batched,
    sort_kernel.bitonic_argsort_batched,
    tunables=_SORT_TUNABLES, switch_measure="last_axis",
    doc="stable last-axis argsort of (..., n) (batched AK sortperm)",
))

topk_p = register(Primitive(
    "topk", _jnp_topk, _pallas_topk,
    tunables=_SORT_TUNABLES, switch_measure="last_axis",
    doc="last-axis top-k values+indices, descending (sort-derived on TPU)",
))


def _jnp_nucleus_mask(x, *, top_p):
    return nucleus_kernel.nucleus_mask_ref(x, top_p=top_p)


def _pallas_nucleus_mask(x, *, top_p):
    return nucleus_kernel.nucleus_mask_blocks(x, top_p=top_p)


nucleus_mask_p = register(Primitive(
    "nucleus_mask", _jnp_nucleus_mask, _pallas_nucleus_mask,
    tunables=_SORT_TUNABLES, switch_measure="last_axis",
    doc="fused top-p keep mask: descending sortperm + softmax prefix sum "
        "+ cut + keep scatter in one registry call (serve sampler hot path)",
))


def _jnp_merge(x, counts=None, *, nruns):
    # oracle = concatenate+sort: the runs are already concatenated, so
    # (count-masked) full sort — O(n log² n), which is exactly what the
    # pallas merge path exists to beat.
    return jnp.sort(merge_kernel.mask_run_tails(x, counts, nruns))


def _pallas_merge(x, counts=None, *, nruns):
    return merge_kernel.kway_merge(x, nruns, counts=counts)


def _jnp_merge_kv(k, v, counts=None, *, nruns, tie_break=False):
    k = merge_kernel.mask_run_tails(k, counts, nruns)
    v = merge_kernel.mask_run_tails(v, counts, nruns,
                                    fill=KC.type_max(v.dtype))
    return kref.sort_kv_ref(k, v, tie_break=tie_break)


def _pallas_merge_kv(k, v, counts=None, *, nruns, tie_break=False):
    return merge_kernel.kway_merge_kv(k, v, nruns, counts=counts,
                                      tie_break=tie_break)


merge_p = register(Primitive(
    "merge", _jnp_merge, _pallas_merge,
    tunables=_SORT_TUNABLES,
    doc="k-way merge of nruns pre-sorted runs (bitonic merge phases only)",
))

merge_kv_p = register(Primitive(
    "merge_kv", _jnp_merge_kv, _pallas_merge_kv,
    tunables=_SORT_TUNABLES,
    doc="key/value k-way merge of nruns pre-sorted runs",
))

searchsorted_p = register(Primitive(
    "searchsorted",
    lambda hay, q, *, side="left": kref.searchsorted_ref(hay, q, side=side),
    lambda hay, q, *, side="left": search_kernel.searchsorted_blocks(
        hay, q, side=side
    ),
    doc="0-based insertion indices into a sorted haystack",
))

minmax_histogram_p = register(Primitive(
    "minmax_histogram", _jnp_minmax_histogram, _pallas_minmax_histogram,
    doc="one-pass (histogram, min, max) — SIHSort's sampling primitive",
))

bincount_p = register(Primitive(
    "bincount", _bincount_impl, None,
    doc="integer-id counts in [0, nbins) via segment_sum (both backends)",
))

# -- segmented primitives over CSR (offsets, values) pairs -----------------
# The ragged generalisation of accumulate/mapreduce/sort (DESIGN.md §10):
# one independent scan/reduce/sort per CSR row, empty rows legal anywhere.
# The MoE bucketed dispatch (models/moe.py) is the resident proof case.

def _jnp_segmented_reduce(values, offsets, *, op, init):
    return segment_kernel.segmented_reduce_ref(op, values, offsets, init=init)


def _pallas_segmented_reduce(values, offsets, *, op, init):
    if values.ndim > 1:
        # feature-lane values (the MoE combine) take the portable flagged
        # path on every backend; the blocked kernel is 1-D
        return segment_kernel.segmented_reduce_ref(
            op, values, offsets, init=init
        )
    return segment_kernel.segmented_reduce_blocks(op, values, offsets,
                                                  init=init)


def _jnp_segmented_scan(values, offsets, *, op, init, inclusive=True):
    return segment_kernel.segmented_scan_ref(
        op, values, offsets, unit=init, exclusive=not inclusive
    )


def _pallas_segmented_scan(values, offsets, *, op, init, inclusive=True):
    if values.ndim > 1:
        return segment_kernel.segmented_scan_ref(
            op, values, offsets, unit=init, exclusive=not inclusive
        )
    return segment_kernel.segmented_scan_blocks(
        op, values, offsets, unit=init, exclusive=not inclusive
    )


def _jnp_segmented_sort(values, offsets, payload=None):
    return segment_kernel.segmented_sort_ref(values, offsets, payload)


def _pallas_segmented_sort(values, offsets, payload=None):
    return segment_kernel.segmented_sort_blocks(values, offsets, payload)


segmented_reduce_p = register(Primitive(
    "segmented_reduce", _jnp_segmented_reduce, _pallas_segmented_reduce,
    doc="per-CSR-segment reduce of (values, offsets) -> (S,) — one flagged "
        "scan pass + segment-end gather on TPU; segment_sum oracle for add",
))

segmented_scan_p = register(Primitive(
    "segmented_scan", _jnp_segmented_scan, _pallas_segmented_scan,
    doc="per-CSR-segment prefix scan (inclusive/exclusive): the dense scan "
        "kernel's carry machinery over (flag, value) pairs, single pass",
))

segmented_sort_p = register(Primitive(
    "segmented_sort", _jnp_segmented_sort, _pallas_segmented_sort,
    tunables=_SORT_TUNABLES,
    doc="per-CSR-segment sort (optional payload): one bitonic kv pass with "
        "segment ids as major key; type-max tail masking like merge",
))

page_gather_p = register(Primitive(
    "page_gather", page_kernel.page_gather_ref, page_kernel.page_gather_blocks,
    tunables=("switch_below", "interpret", "page_size"),
    tuning_defaults={"page_size": 8},
    doc="paged KV-cache gather: pages (P, ps, ...) + block table (B, T) -> "
        "logical (B, T*ps, ...); scalar-prefetch BlockSpec indirection on "
        "TPU. Owns the ``page_size`` knob the paged engine resolves.",
))
