"""The host allocator's policy for jobs that move gigabytes through NumPy.

glibc's ``malloc`` serves a large block (from 128 KiB, and always past 32
MiB) with an ``mmap`` of its own and unmaps it again on ``free``, so each job that allocates and
frees gigabytes of host columns (a records job's payload gather, its word
columns and wire rows) pays for every one of those pages to be faulted in
and zeroed once more by the kernel.  :func:`keep_freed_memory` has the
process keep what it frees for its next allocations instead: no block is
served by its own ``mmap`` and the heap is never trimmed.  The process then
holds its peak host memory between jobs, and a job of the same size as the
last one reuses those pages (timings on a TPU v5e's host: PERF.md,
Findings).
"""

from __future__ import annotations

import ctypes

#: ``mallopt`` parameters, from glibc's ``malloc.h``.
_M_TRIM_THRESHOLD = -1
_M_MMAP_MAX = -4

_kept = False


def keep_freed_memory() -> bool:
    """Have ``malloc`` keep freed memory for the process's next allocations.

    Process-wide and permanent: ``M_MMAP_MAX`` 0 (no block gets its own
    ``mmap``) and ``M_TRIM_THRESHOLD`` -1 (the heap is never trimmed).
    Idempotent.  Returns whether the policy holds; False where the C library
    has no ``mallopt`` (not glibc) or refused a setting.
    """
    global _kept
    if _kept:
        return True
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
    mallopt.restype = ctypes.c_int
    _kept = mallopt(_M_MMAP_MAX, 0) == 1 and mallopt(_M_TRIM_THRESHOLD, -1) == 1
    return _kept
