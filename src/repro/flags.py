"""Process-wide switches: the paper-faithful-baseline A/B toggle plus the
kernel-dispatch environment knobs consumed by ``repro.kernels.api``.

``REPRO_BASELINE=1`` re-enables every pre-hillclimb code path so the
baseline can be re-measured under the *final* analyzer convention
(before/after numbers must share one accounting):

* attention: f32 HBM upcasts of Q/K/V/P before the dots (vs C1-inline
  bf16-into-MXU);
* decode: ys-stacked cache re-materialization (vs stacked-carry in-place
  token writes);
* sharding: seq-sharded serve KV when kv%tp != 0 (vs head_dim-sharded);
* MoE: global-token dispatch (vs GShard grouped);
* sLSTM: gate projections inside the timestep scan (vs hoisted Wx).

Dispatch knobs (read at dispatch time, not import time, so tests can
monkeypatch ``os.environ``):

* ``REPRO_KERNEL_BACKEND`` — force every op onto one backend
  (``pallas`` | ``xla`` | ``ref``), bypassing the ACCEL/HOST control law;
* ``REPRO_VMEM_BUDGET``    — default LMM/VMEM byte budget for the
  offload decision and the Pallas block selection;
* ``REPRO_ALLOW_PALLAS``   — ``1``/``0``: whether the ACCEL decision may
  bind to the Pallas backend (default: only on real TPU — on CPU the
  interpreter is a correctness tool, not a fast path);
* ``REPRO_INTERPRET``      — ``1``/``0``: run Pallas kernels in
  interpreter mode (default: on unless running on TPU);
* ``REPRO_PLATFORM``       — name of a registered hardware platform
  (``repro.platforms``); ``DispatchContext.from_env`` derives its
  budget/policy/pallas-eligibility from the platform, with the explicit
  knobs above still winning where set.
"""

import os
import pathlib

BASELINE = os.environ.get("REPRO_BASELINE", "") == "1"

DEFAULT_VMEM_BUDGET = 4 * 1024 * 1024

_VALID_BACKENDS = ("pallas", "xla", "ref")


def _env_bool(name: str):
    v = os.environ.get(name, "").strip().lower()
    if v == "":
        return None
    return v not in ("0", "false", "no")


def _on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def kernel_backend_override():
    """Global backend force from REPRO_KERNEL_BACKEND, or None."""
    v = os.environ.get("REPRO_KERNEL_BACKEND", "").strip().lower()
    if not v:
        return None
    if v not in _VALID_BACKENDS:
        raise ValueError(
            f"REPRO_KERNEL_BACKEND={v!r}: expected one of {_VALID_BACKENDS}")
    return v


def vmem_budget_override():
    """Explicit REPRO_VMEM_BUDGET byte count, or None when unset."""
    v = os.environ.get("REPRO_VMEM_BUDGET", "")
    if not v:
        return None
    try:
        return int(v)
    except ValueError:
        raise ValueError(
            f"REPRO_VMEM_BUDGET={v!r}: expected an integer byte count"
        ) from None


def vmem_budget_default() -> int:
    v = vmem_budget_override()
    return DEFAULT_VMEM_BUDGET if v is None else v


def platform_default():
    """Platform name from REPRO_PLATFORM, or None. Resolved against the
    ``repro.platforms`` registry by ``DispatchContext.from_env``."""
    return os.environ.get("REPRO_PLATFORM", "").strip() or None


def allow_pallas_default() -> bool:
    v = _env_bool("REPRO_ALLOW_PALLAS")
    return _on_tpu() if v is None else v


def interpret_default() -> bool:
    v = _env_bool("REPRO_INTERPRET")
    return (not _on_tpu()) if v is None else v


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here. Otherwise the cache lives at the
    fixed path ``<checkout>/.jax_cache``: the directory is part of each
    entry's key, so it never moves between runs. Entry points call this
    (``chip_smoke.py``, the ``repro.launch`` CLIs); importing a module
    never does."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    # cache every program: a warm run should compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d
