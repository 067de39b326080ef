"""The JAX runtime as this process sees it: where compiled programs are
kept, what compiling has cost so far, and which devices answered.

One place for all three because every entry point that compiles needs
the same answers before its first compile: the server
(`ctl.main server`) and `chip_smoke.py` call `setup_compile_cache()`;
`/debug/vars`
serves `snapshot()` so a client that never imports JAX can still name
the platform its answers came from.

Compile cost is read from JAX's own monitoring events, not from a
bracket around the program builders: `jax.jit` compiles lazily at the
first call, inside the launch, so a timer around the builder sees
microseconds while the query that triggered it waits seconds.
"""

from __future__ import annotations

import functools
import os
import sys
import threading
from typing import Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

_mu = threading.Lock()
_listening = False
_compile = {"backend_compiles": 0, "backend_compile_s": 0.0,
            "cache_hits": 0, "cache_misses": 0}


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    if event == _BACKEND_COMPILE:
        with _mu:
            _compile["backend_compiles"] += 1
            _compile["backend_compile_s"] += float(duration_secs)


def _on_event(event: str, **_kw) -> None:
    key = {_CACHE_HIT: "cache_hits", _CACHE_MISS: "cache_misses"}.get(event)
    if key is not None:
        with _mu:
            _compile[key] += 1


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache and start counting
    compiles. Call before the first compile; idempotent.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX has already taken the
    directory from it and no code here names another. Otherwise the
    cache lives at `<checkout>/.jax_cache`: a fixed path, because the
    path is part of what a cache hit depends on, so a directory named
    after a pid or a temp dir never hits. Programs are cached however
    fast they compiled (JAX's default skips those under a second,
    which is most of this repo's): a cold start pays every one of them
    again. Returns the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_REPO_ROOT, ".jax_cache"))
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    global _listening
    with _mu:
        first, _listening = not _listening, True
    if first:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
    return str(jax.config.jax_compilation_cache_dir)


@functools.lru_cache(maxsize=None)
def _dist_version(name: str) -> Optional[str]:
    from importlib import metadata

    try:
        return metadata.version(name)
    except metadata.PackageNotFoundError:
        return None


def snapshot() -> Optional[dict]:
    """Versions, devices, cache directory and compile totals, or None
    while this process has not imported JAX (a host-only server never
    does, and asking must not make it)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    devs = jax.devices()
    with _mu:
        comp = dict(_compile, counted=_listening)
    comp["backend_compile_s"] = round(comp["backend_compile_s"], 3)
    memory = {}
    for d in jax.local_devices():  # another process's device has none
        ms = d.memory_stats() or {}
        memory[str(d)] = {k: int(ms[k]) for k in
                          ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                          if k in ms}
    return {
        "jax": jax.__version__,
        "jaxlib": _dist_version("jaxlib"),
        "libtpu": _dist_version("libtpu"),
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "compile": comp,
        "memory": memory,
    }
