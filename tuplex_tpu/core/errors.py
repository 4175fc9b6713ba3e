"""Exception-code lattice for dual-mode execution.

On device, every fused pipeline computes a per-row int32 error code alongside
its outputs; code 0 means the row took the normal path. Non-zero rows are
masked out of device outputs and shipped to the interpreter resolve path.

Re-designs the reference's exception-code enum + exception partitions
(reference: tuplex/utils/include/ExceptionCodes.h:24-118, compiled branch to
exception_handler_f at core/include/physical/CodeDefs.h:43) as a vectorized
code lattice: composed ops propagate the FIRST error per row (lower op index
wins), matching sequential Python semantics.
"""

from __future__ import annotations

import enum


class ExceptionCode(enum.IntEnum):
    OK = 0
    # Python exception classes reproducible by compiled paths
    ZERODIVISIONERROR = 1
    VALUEERROR = 2
    TYPEERROR = 3
    INDEXERROR = 4
    KEYERROR = 5
    ATTRIBUTEERROR = 6
    OVERFLOWERROR = 7
    STOPITERATION = 8
    ASSERTIONERROR = 9
    # internal codes (reference: ExceptionCodes.h NORMALCASEVIOLATION etc.)
    NORMALCASEVIOLATION = 100
    BADPARSE_STRING_INPUT = 101
    NULLERROR = 102            # unexpected None on a non-Option path
    GENERALCASEVIOLATION = 103
    LOOPCAPEXCEEDED = 104      # while-loop unroll cap hit: interpreter row
    PYTHON_FALLBACK = 110      # UDF not compilable: row routed to interpreter
    UNKNOWN = 120


_PY_TO_CODE = {
    ZeroDivisionError: ExceptionCode.ZERODIVISIONERROR,
    ValueError: ExceptionCode.VALUEERROR,
    TypeError: ExceptionCode.TYPEERROR,
    IndexError: ExceptionCode.INDEXERROR,
    KeyError: ExceptionCode.KEYERROR,
    AttributeError: ExceptionCode.ATTRIBUTEERROR,
    OverflowError: ExceptionCode.OVERFLOWERROR,
    StopIteration: ExceptionCode.STOPITERATION,
    AssertionError: ExceptionCode.ASSERTIONERROR,
}

_CODE_TO_PY = {v: k for k, v in _PY_TO_CODE.items()}


def code_for_exception(exc: BaseException) -> ExceptionCode:
    for cls in type(exc).__mro__:
        if cls in _PY_TO_CODE:
            return _PY_TO_CODE[cls]
    return ExceptionCode.UNKNOWN


_CODE_INT_TO_PY = {int(c): _CODE_TO_PY.get(c) for c in ExceptionCode}


def exception_class_for_code(code: int):
    """Python exception class for a code (None for internal codes). Plain
    dict lookup: enum construction showed up at 0.3s/1M rows on the
    exact-exception exit."""
    return _CODE_INT_TO_PY.get(code)


_CODE_INT_TO_NAME = {
    int(c): (_CODE_TO_PY[c].__name__ if c in _CODE_TO_PY else c.name)
    for c in ExceptionCode
}


def exception_name(code: int) -> str:
    name = _CODE_INT_TO_NAME.get(code)
    return name if name is not None else f"code{code}"


def code_for_exception_class(cls):
    """ExceptionCode for an exception CLASS (mro-aware, like
    code_for_exception but without a live instance), or None when no
    compiled-path code maps exactly — base classes like Exception or
    LookupError return None, which callers must treat as "covers
    anything" (the dead-resolver lint skips them)."""
    for c in getattr(cls, "__mro__", ()):
        if c in _PY_TO_CODE:
            return _PY_TO_CODE[c]
    return None


def code_for_name(name: str):
    """ExceptionCode for a Python exception-class NAME ('ValueError' →
    VALUEERROR), or None when no compiled-path code exists for it. Static
    analysis maps `raise X` sites through this without a live exception
    instance (compiler/analyzer.py exception-site inventory)."""
    return ExceptionCode.__members__.get(name.upper()) if name else None


# Packed device-lattice layout: exception-class code in the low byte, the
# operator above it. One int32 per row carries both — a second per-row
# operator lattice measured a 20x kLoop recompute pathology on XLA-CPU.
# On the device the operator is its 1-based POSITION in its stage (a
# stage's jaxpr must not depend on the session's operator counter); the
# host maps it onto the current job's `op.id` as the lattice is fetched
# (plan/physical.TransformStage.op_ids_of_lattice), in the same layout. A
# value that would overflow the 23 bits left in an int32 packs as 0
# ("unknown operator") — attribution degrades, correctness (the class
# code) never does.
_OP_ID_LIMIT = 1 << 23


def pack_device_code(code: int, op_id: int) -> int:
    if not 0 < op_id < _OP_ID_LIMIT:
        op_id = 0
    return int(code) | (op_id << 8)


def unpack_device_code(packed: int) -> tuple[int, int]:
    """packed -> (exception-class code, operator id)."""
    return packed & 0xFF, packed >> 8


def unpack_device_codes(codes):
    """Vectorized unpack over a numpy int array -> iterator of (code,
    op_id) tuples. Same layout as unpack_device_code; per-row python calls
    measurably hurt at zillow's ~6% error-row rate."""
    return zip((codes & 0xFF).tolist(), (codes >> 8).tolist())


class TuplexException(Exception):
    """Driver-side framework error (not a per-row exception)."""


class NotCompilable(TuplexException):
    """Raised by the emitter when a UDF uses constructs outside the compiled
    subset; the operator then runs rows on the interpreter path (reference:
    fallback mode, python/tests/test_fallback.py semantics)."""
