"""Shared integer env-knob parser for the kernel budget/eligibility
knobs (CUVITE_REBIN_MAX_ELEMS, CUVITE_SCHED_BUDGET).

One definition so the parse/warn/default behavior cannot drift between
copies: accepts 0x/0b prefixes (``int(raw, 0)``), warns loudly on
malformed or out-of-range values and falls back to the default — a
typo'd knob must never silently measure the baseline while the
operator believes it changed (the CUVITE_EXCHANGE_CUTOVER precedent).

Note: ``louvain/bucketed.py::_env_int`` (the historical width-ladder
knob parser) predates this helper with slightly different semantics
(base-10 only, no range check) and keeps them for compatibility; new
knobs should use this one.
"""

from __future__ import annotations

import os
import warnings


def request_host_devices(n: int) -> None:
    """Ask XLA for ``n`` virtual CPU devices (batch-axis sharding,
    ISSUE 9).  Must run BEFORE jax backend init — the flag is read once
    at first backend touch — so CLI entry points call this right after
    argument parsing and before any jax import.  No-op when ``n <= 1``
    or when a device-count flag is already present (the test conftest,
    an operator's explicit XLA_FLAGS): never silently override an
    existing request."""
    if n <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()


def env_int(name: str, default: int, *, minimum: int = 1,
            maximum: int | None = None) -> int:
    """``int(os.environ[name], 0)`` clamped to [minimum, maximum], or
    ``default`` (with a warning) when unset-empty, malformed, or out of
    range."""
    raw = os.environ.get(name)
    if not raw:
        return default
    try:
        v = int(raw, 0)
    except ValueError:
        v = None
    if v is None or v < minimum or (maximum is not None and v > maximum):
        bound = (f" <= {maximum}" if maximum is not None else "")
        warnings.warn(
            f"malformed {name}={raw!r} (want an integer >= {minimum}"
            f"{bound}); using the default {default}", stacklevel=2)
        return default
    return v
