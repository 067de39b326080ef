"""Startup auto-calibration for the count-backend dispatch.

Which count backend is faster — the Pallas streaming kernels or the
XLA gather+fold programs — depends on the kernel shape and the chip,
and the CSA epilogue (kernels.csa_popcount_sum) only pays when the backend's
population_count lowering is multi-op. A hardcoded default is wrong on
somebody's chip, so nobody hardcodes: `PILOSA_TPU_COUNT_BACKEND=auto`
(now the default) measures BOTH backends once per process on a
representative uniform coarse-count shape and the winner earns the
dispatch.

Safety: a Pallas compile can hang or fail, so the measurement
runs in an abandonable daemon thread under a bounded wait
(PILOSA_TPU_CALIBRATE_TIMEOUT_S, default 120 s) and starts with the
trivial-kernel canary (kernels.pallas_probe_ok). Any hang, probe
failure, or exception verdicts "xla" — the always-safe backend — and
caches that, with the cause in the record's `source` (and `error`):
`chip_smoke.py` accepts only `measured`. Queries arriving mid-calibration are served on xla by
callers that pass wait=False.

Persistence: PILOSA_TPU_CALIBRATION_FILE names a JSON file keyed by
device kind; a fresh process on the same hardware reuses the stored
verdict instead of re-measuring (source "cache-file"). The full
record — both timings, shape, device, winner, source — is surfaced at
/debug/vars under "count_calibration" (api/handler._get_expvar).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Optional

_MU = threading.Lock()
_RESULT: Optional[dict] = None
_SPARSE_RESULT: Optional[dict] = None

# The headline Intersect+Count composition (plan._tree_signature form).
_TREE = ["and", ["leaf", 0], ["leaf", 1]]


def _env_backend() -> str:
    v = os.environ.get("PILOSA_TPU_COUNT_BACKEND", "auto").lower()
    return v if v in ("pallas", "pallas_interpret", "xla", "auto") else "auto"


def _timeout_s() -> float:
    try:
        return float(os.environ.get("PILOSA_TPU_CALIBRATE_TIMEOUT_S", "120"))
    except ValueError:
        return 120.0


def _device_key() -> str:
    import jax

    try:
        dev = jax.devices()[0]
        return f"{jax.default_backend()}:{dev.device_kind}"
    except Exception:  # noqa: BLE001 — uninitialized backend
        return "unknown"


def _cache_load(key: str) -> Optional[dict]:
    path = os.environ.get("PILOSA_TPU_CALIBRATION_FILE")
    if not path:
        return None
    try:
        with open(path, encoding="utf-8") as f:
            rec = json.load(f).get(key)
    except (OSError, ValueError):
        return None
    if not isinstance(rec, dict) or rec.get("backend") not in ("pallas",
                                                               "xla"):
        return None
    rec = dict(rec)
    rec["source"] = "cache-file"
    return rec


def _cache_store(key: str, rec: dict) -> None:
    path = os.environ.get("PILOSA_TPU_CALIBRATION_FILE")
    if not path:
        return
    try:
        data = {}
        try:
            with open(path, encoding="utf-8") as f:
                data = json.load(f)
        except (OSError, ValueError):
            pass
        if not isinstance(data, dict):
            data = {}
        data[key] = rec
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:  # best-effort: a read-only FS just re-measures
        pass


def _best_ms(fn: Callable, *args, warmup: int = 2, iters: int = 5) -> float:
    """Best-of-k wall ms of fn(*args) with device completion."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _measure(interpret: bool) -> dict:
    """Time Pallas vs XLA on a representative uniform coarse count.

    The problem is the serving hot path in miniature: a dense
    (S, cap, 2048) uint32 pool, two leaves at uniform row-run indices,
    Intersect+Count. Pallas runs kernels.coarse_count_uniform (the
    multi-slice-fetch kernel the uniform serving programs wrap); XLA
    runs the equivalent jitted dynamic-slice gather + fold + popcount.
    Shapes shrink via env for tests; interpret=True (the forced
    non-TPU path) shrinks further so CI measures in milliseconds, not
    minutes.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from .kernels import coarse_count_uniform
    from .pool import CONTAINER_WORDS, ROW_SPAN

    def _env_int(name: str, default: int) -> int:
        try:
            return max(1, int(os.environ.get(name, str(default))))
        except ValueError:
            return default

    s_n = _env_int("PILOSA_TPU_CALIBRATE_SLICES", 8 if interpret else 64)
    runs = _env_int("PILOSA_TPU_CALIBRATE_ROWS", 2 if interpret else 8)
    cap = runs * ROW_SPAN
    rng = np.random.default_rng(0x9E3779B9)
    pool = jnp.asarray(rng.integers(
        0, 1 << 32, size=(s_n, cap, CONTAINER_WORDS), dtype=np.uint32))
    starts = jnp.asarray([0, runs - 1], dtype=jnp.int32)

    pallas_fn = jax.jit(lambda w, s: coarse_count_uniform(
        (w, w), s, _TREE, interpret=interpret))

    @jax.jit
    def xla_fn(w, s):
        a = lax.dynamic_slice_in_dim(w, s[0] * ROW_SPAN, ROW_SPAN, 1)
        b = lax.dynamic_slice_in_dim(w, s[1] * ROW_SPAN, ROW_SPAN, 1)
        return jnp.sum(lax.population_count(a & b).astype(jnp.int32),
                       axis=(1, 2))

    # Cross-check before timing: a backend that answers WRONG must not
    # win a race. Mismatch raises; the watchdog wrapper verdicts xla.
    want = np.asarray(xla_fn(pool, starts)).reshape(-1)
    got = np.asarray(pallas_fn(pool, starts)).reshape(-1)
    if not np.array_equal(want, got):
        raise AssertionError(
            f"calibration cross-check mismatch: xla={want[:4]}... "
            f"pallas={got[:4]}...")

    pallas_ms = _best_ms(pallas_fn, pool, starts)
    xla_ms = _best_ms(xla_fn, pool, starts)
    return {
        "backend": "pallas" if pallas_ms <= xla_ms else "xla",
        "source": "measured",
        "pallas_ms": round(pallas_ms, 4),
        "xla_ms": round(xla_ms, 4),
        "shape": {"slices": s_n, "capacity": cap},
        "interpret": interpret,
    }


def calibrate_count_backend(force_measure: bool = False) -> dict:
    """Resolve (measuring if needed) the auto count backend.

    Returns the process-wide calibration record. On non-TPU backends
    the verdict is an instant "xla" (source "non-tpu") — tier-1 CPU
    runs must not pay a measurement — unless `force_measure` or
    PILOSA_TPU_CALIBRATE=force asks for a real (interpret-mode)
    measurement, which is how the CI smoke test exercises the
    machinery end to end. On TPU: probe canary, then measurement, all
    inside a daemon thread abandoned on timeout (verdict "xla").
    """
    global _RESULT
    with _MU:
        if _RESULT is not None:
            return _RESULT
        import jax

        t0 = time.perf_counter()
        key = _device_key()
        on_tpu = jax.default_backend() == "tpu"
        forced = force_measure or (
            os.environ.get("PILOSA_TPU_CALIBRATE", "").lower() == "force")
        rec: Optional[dict] = None
        if not on_tpu and not forced:
            rec = {"backend": "xla", "source": "non-tpu"}
        if rec is None:
            rec = _cache_load(key)
        if rec is None:
            box: dict = {}
            done = threading.Event()

            def work():
                from ..obs.health import HEALTH
                try:
                    from .kernels import pallas_probe_ok

                    # Visibility-only bracket (base=None): the caller
                    # already bounds this with done.wait(timeout) and
                    # abandons a hung compile, so the watchdog never
                    # judges it — but /debug/health shows what the
                    # abandoned thread is stuck in.
                    with HEALTH.inflight("calibrate", "measure"):
                        if on_tpu and not pallas_probe_ok():
                            box["rec"] = {"backend": "xla",
                                          "source": "probe-failed"}
                        else:
                            box["rec"] = _measure(interpret=not on_tpu)
                except Exception as e:  # noqa: BLE001 — any failure
                    # means the safe backend, with the reason recorded
                    box["rec"] = {"backend": "xla", "source": "error",
                                  "error": f"{type(e).__name__}: {e}"}
                finally:
                    done.set()

            threading.Thread(target=work, daemon=True,
                             name="count-calibrate").start()
            if done.wait(_timeout_s()):
                rec = box["rec"]
            else:  # hung compile: abandon the thread, pin pallas off
                rec = {"backend": "xla", "source": "timeout"}
            if rec.get("source") == "measured":
                _cache_store(key, rec)
        rec["device"] = key
        rec["elapsed_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        _RESULT = rec
        return rec


def calibrated_backend(wait: bool = True) -> str:
    """The resolved "auto" backend. wait=False returns the provisional
    "xla" instead of blocking behind an in-flight calibration (the
    serving layer's arriving-during-probe policy)."""
    rec = _RESULT
    if rec is not None:
        return rec["backend"]
    if not wait and _MU.locked():
        return "xla"
    return calibrate_count_backend()["backend"]


def resolve_backend(wait: bool = True) -> str:
    """Full dispatch resolution: the PILOSA_TPU_COUNT_BACKEND pin when
    set, else the calibrated winner. This is what kernels.use_pallas
    and the serving layer's backend switch consult."""
    v = _env_backend()
    if v != "auto":
        return v
    return calibrated_backend(wait=wait)


def calibration_snapshot() -> Optional[dict]:
    """The current record (None before first resolution) — /debug/vars
    surface, satisfying "the measurement recorded in /debug/vars". The
    sorted-array race result rides along under "sparse" once resolved."""
    rec = _RESULT
    if rec is None:
        return None
    out = dict(rec)
    if _SPARSE_RESULT is not None:
        out["sparse"] = dict(_SPARSE_RESULT)
    return out


# -- sorted-array (sparse container) backend race -----------------------------
#
# The array×array intersect-count has the same two-backend shape as the
# dense count path — an XLA binary-search gather ladder
# (bitops.sparse_pair_intersect_counts) vs a Pallas broadcast-compare
# kernel (kernels.pallas_sparse_pair_counts) — and the same "which wins
# is hardware-dependent" problem: gathers are costly on TPU while VPU
# compares are nearly free, but the compare kernel's work grows with
# K^2. Same machinery, separate verdict: PILOSA_TPU_SPARSE_BACKEND pins
# it, else one race per process on a representative container block.


def _env_sparse_backend() -> str:
    v = os.environ.get("PILOSA_TPU_SPARSE_BACKEND", "auto").lower()
    return v if v in ("pallas", "xla", "auto") else "auto"


def _measure_sparse(interpret: bool) -> dict:
    """Time Pallas vs XLA on a representative sorted-array intersect:
    a slab of half-full containers at the break-even K, cross-checked
    before timing (a wrong backend must not win)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .bitops import sparse_pair_intersect_counts
    from .kernels import pallas_sparse_pair_counts

    n = 16 if interpret else 512
    k = 128 if interpret else 512
    rng = np.random.default_rng(0x9E3779B9)

    def block():
        vals = np.full((n, k), 0xFFFF, np.uint16)
        lens = rng.integers(0, k + 1, size=n).astype(np.int32)
        for i, ln in enumerate(lens):
            vals[i, :ln] = np.sort(
                rng.choice(1 << 16, size=ln, replace=False)).astype(np.uint16)
        return jnp.asarray(vals), jnp.asarray(lens)

    a, al = block()
    b, bl = block()
    xla_fn = jax.jit(sparse_pair_intersect_counts)
    pallas_fn = lambda *args: pallas_sparse_pair_counts(  # noqa: E731
        *args, interpret=interpret)

    want = np.asarray(xla_fn(a, al, b, bl))
    got = np.asarray(pallas_fn(a, al, b, bl))
    if not np.array_equal(want, got):
        raise AssertionError(
            f"sparse calibration cross-check mismatch: xla={want[:4]}... "
            f"pallas={got[:4]}...")

    pallas_ms = _best_ms(pallas_fn, a, al, b, bl)
    xla_ms = _best_ms(xla_fn, a, al, b, bl)
    return {
        "backend": "pallas" if pallas_ms <= xla_ms else "xla",
        "source": "measured",
        "pallas_ms": round(pallas_ms, 4),
        "xla_ms": round(xla_ms, 4),
        "shape": {"containers": n, "values": k},
        "interpret": interpret,
    }


def calibrate_sparse_backend(force_measure: bool = False) -> dict:
    """Resolve (measuring if needed) the auto sorted-array backend —
    the sparse twin of calibrate_count_backend, with the same safety
    ladder: instant "xla" off-TPU, probe canary, watchdogged daemon
    measurement, any failure verdicts "xla"."""
    global _SPARSE_RESULT
    with _MU:
        if _SPARSE_RESULT is not None:
            return _SPARSE_RESULT
        import jax

        t0 = time.perf_counter()
        key = f"{_device_key()}/sparse"
        on_tpu = jax.default_backend() == "tpu"
        forced = force_measure or (
            os.environ.get("PILOSA_TPU_CALIBRATE", "").lower() == "force")
        rec: Optional[dict] = None
        if not on_tpu and not forced:
            rec = {"backend": "xla", "source": "non-tpu"}
        if rec is None:
            rec = _cache_load(key)
        if rec is None:
            box: dict = {}
            done = threading.Event()

            def work():
                try:
                    from .kernels import pallas_probe_ok

                    if on_tpu and not pallas_probe_ok():
                        box["rec"] = {"backend": "xla",
                                      "source": "probe-failed"}
                    else:
                        box["rec"] = _measure_sparse(interpret=not on_tpu)
                except Exception as e:  # noqa: BLE001
                    box["rec"] = {"backend": "xla", "source": "error",
                                  "error": f"{type(e).__name__}: {e}"}
                finally:
                    done.set()

            threading.Thread(target=work, daemon=True,
                             name="sparse-calibrate").start()
            if done.wait(_timeout_s()):
                rec = box["rec"]
            else:
                rec = {"backend": "xla", "source": "timeout"}
            if rec.get("source") == "measured":
                _cache_store(key, rec)
        rec["device"] = key
        rec["elapsed_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        _SPARSE_RESULT = rec
        return rec


def resolve_sparse_backend(wait: bool = True) -> str:
    """Dispatch resolution for the sorted-array kernels: the
    PILOSA_TPU_SPARSE_BACKEND pin when set, else the raced winner
    (provisional "xla" while a calibration is in flight and
    wait=False)."""
    v = _env_sparse_backend()
    if v != "auto":
        return v
    rec = _SPARSE_RESULT
    if rec is not None:
        return rec["backend"]
    if not wait and _MU.locked():
        return "xla"
    return calibrate_sparse_backend()["backend"]


def reset_for_tests() -> None:
    global _RESULT, _SPARSE_RESULT
    with _MU:
        _RESULT = None
        _SPARSE_RESULT = None
