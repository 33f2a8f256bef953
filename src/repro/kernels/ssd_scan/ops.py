"""Public entry points for the SSD scan.

``ssd``/``ssd_extend``/``ssd_step`` dispatch to the Pallas TPU kernel or
to the pure-jnp oracle via ``kernels.dispatch`` (backend default +
``REPRO_FORCE_REF``/``REPRO_FORCE_PALLAS`` env overrides); the oracle is
also what multi-pod dry-runs lower, since Pallas CPU lowering is not
representative of TPU codegen.
"""
from __future__ import annotations

import warnings

import jax

from repro.kernels import dispatch
from repro.kernels.ssd_scan import ref as _ref


def set_use_pallas(flag: bool) -> None:
    """Deprecated no-op shim. The module-scoped ssd-only override is
    retired: implementation choice goes through ``kernels.dispatch``
    like every other op — pass ``use_pallas=`` per call, or set
    ``REPRO_FORCE_REF``/``REPRO_FORCE_PALLAS`` process-wide."""
    warnings.warn(
        "ssd_scan.ops.set_use_pallas is deprecated and has no effect; "
        "pass use_pallas= per call or use the REPRO_FORCE_* env vars "
        "(kernels.dispatch).", DeprecationWarning, stacklevel=2)


def ssd(x, dt, A, B, C, D=None, *, chunk=64, initial_state=None,
        use_pallas=None):
    use, interpret = dispatch.resolve(use_pallas)
    if use and initial_state is None:
        from repro.kernels.ssd_scan import kernel as _k
        return _k.ssd_pallas(x, dt, A, B, C, D, chunk=chunk,
                             initial_state=None, interpret=interpret)
    return _ref.ssd_reference(x, dt, A, B, C, D, chunk=chunk,
                              initial_state=initial_state)


# Tokens per block of the chunked extend (timed on a TPU v5e at the
# mamba2-780m admission chunk against 256, PERF.md).
EXTEND_CHUNK = 128


def ssd_extend(state, x, dt, A, B, C, D=None, *, use_pallas=None):
    """Multi-token recurrence from an explicit state — the serving
    engine's chunked-admission / speculative-verify form.

    The jnp reference is token-serial: bitwise equal to T applications
    of ``ssd_step``. The Pallas path picks its form from T: a whole
    multiple of ``EXTEND_CHUNK`` tokens (an admission or replay chunk)
    runs the chunked dual form on the MXU from the carried state, which
    agrees with the serial recurrence numerically, not bitwise; any
    other T (decode rows, verify windows) runs the token-serial kernel,
    bitwise equal to the serial steps. Both keep a row whose ``dt`` is
    all zero exactly as it was."""
    use, interpret = dispatch.resolve(use_pallas)
    if use:
        from repro.kernels.ssd_scan import kernel as _k
        T = x.shape[1]
        if T >= EXTEND_CHUNK and T % EXTEND_CHUNK == 0:
            return _k.ssd_extend_chunked_pallas(
                state, x, dt, A, B, C, D, chunk=EXTEND_CHUNK,
                interpret=interpret)
        return _k.ssd_extend_pallas(state, x, dt, A, B, C, D,
                                    interpret=interpret)
    return _ref.ssd_extend_reference(state, x, dt, A, B, C, D)


def ssd_step(state, x, dt, A, B, C, D=None):
    return _ref.ssd_decode_step(state, x, dt, A, B, C, D)
