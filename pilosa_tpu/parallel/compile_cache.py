"""Persistent XLA compilation cache wiring.

Every kernel the planner compiles — stack assembly, fused count, BSI
aggregates — is a pure function of padded array shapes, so a restarted
node re-deriving the exact same programs pays full trace+compile cost
for zero new information. JAX ships an on-disk compilation cache that
memoizes backend_compile across processes; this module turns it on and
exposes deterministic hit/miss counters so warmup, /debug/vars, the
benchmark and CI can all assert the cache actually did its job instead
of trusting wall-clock deltas.

The directory is part of the cache key, so a directory that moves
never hits. ``resolve_dir`` fixes the order: JAX_COMPILATION_CACHE_DIR
where the caller set it (JAX reads it itself, and this module then
sets no directory of its own), else an explicit directory, else one
fixed path in the checkout.

The JAX knobs are process-global, so ``enable`` is idempotent: the
first call fixes the directory, later calls (second ServerNode in one
test process) just attach additional stats sinks. Defaults are tuned
for this workload: the stock ``min_compile_time_secs`` of 1.0 would
skip every kernel we have (they compile in milliseconds on CPU), so
both persistence thresholds are dropped to zero.
"""

from __future__ import annotations

import os
import threading

#: where the cache lives when nobody says otherwise: one fixed path in
#: the checkout (listed in .gitignore), never a per-run temp dir.
DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")

_EVENT_HIT = "/jax/compilation_cache/cache_hits"
_EVENT_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"

_lock = threading.Lock()
_enabled_dir: str | None = None
_listener_installed = False
_counters = {"hits": 0, "requests": 0}
# External Stats objects (ServerNode.stats) that mirror the counters so
# they surface on /debug/vars without the node polling this module.
_sinks: list = []


def _listener(event: str, **kwargs) -> None:
    if event == _EVENT_HIT:
        name = "compileCache.hits"
        key = "hits"
    elif event == _EVENT_REQUEST:
        name = "compileCache.requests"
        key = "requests"
    else:
        return
    with _lock:
        _counters[key] += 1
        sinks = list(_sinks)
    for s in sinks:
        try:
            s.count(name, 1)
        except Exception:
            pass  # a broken sink must not poison compilation


def resolve_dir(explicit: str | None = None) -> str:
    """The directory the persistent cache lives in ("" = disabled).

    ``explicit`` is a caller's choice (``--compile-cache-dir``): "off"
    disables, a path is used as given. JAX_COMPILATION_CACHE_DIR, where
    set, overrides any path: whoever runs the program placed the cache
    from outside and expects the next run to find it there.
    """
    if explicit == "off":
        return ""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or explicit or DEFAULT_DIR)


def enable(cache_dir: str, stats=None) -> bool:
    """Turn on JAX's persistent compilation cache at ``cache_dir``, the
    directory ``resolve_dir`` returned; "" ("off") turns it off.

    Returns True when the cache is active (this call or a prior one).
    ``stats`` (a Stats-protocol object) is registered as a counter sink
    either way. A directory JAX already points at (the caller's
    JAX_COMPILATION_CACHE_DIR, which JAX reads itself) is left alone:
    the program then sets no directory of its own.
    """
    global _enabled_dir, _listener_installed
    if stats is not None:
        with _lock:
            if stats not in _sinks:
                _sinks.append(stats)
    with _lock:
        already = _enabled_dir
    if already is not None:
        return True
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not cache_dir:
        # JAX honours JAX_COMPILATION_CACHE_DIR by itself (stock
        # thresholds, no counters), so "off" has to say so.
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        return False
    if jax.config.jax_compilation_cache_dir != cache_dir:
        try:
            os.makedirs(cache_dir, exist_ok=True)
        except OSError:
            # Read-only checkout: the node still boots, uncached, and
            # stats() reports enabled=False.
            return False
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    # The stock thresholds (1.0 s / small-entry floor) exist for
    # giant ML programs; our kernels compile in milliseconds and
    # every one of them is on the cold path, so persist them all.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # JAX initializes its cache singleton at most once per process,
    # on the first compile. Anything that compiled before this call
    # (module-import constant folding, another subsystem's jit)
    # froze it with the settings of that moment — reset so the next
    # compile re-initializes against ours.
    compilation_cache.reset_cache()
    with _lock:
        if not _listener_installed:
            jax.monitoring.register_event_listener(_listener)
            _listener_installed = True
        _enabled_dir = cache_dir
    return True


def stats() -> dict:
    """Snapshot: {'enabled', 'dir', 'hits', 'requests'}."""
    with _lock:
        return {
            "enabled": _enabled_dir is not None,
            "dir": _enabled_dir or "",
            "hits": _counters["hits"],
            "requests": _counters["requests"],
        }


def detach(stats_obj) -> None:
    """Drop a previously attached stats sink (node close)."""
    with _lock:
        try:
            _sinks.remove(stats_obj)
        except ValueError:
            pass
