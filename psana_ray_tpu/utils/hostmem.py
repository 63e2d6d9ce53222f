"""Host allocator tuning for MB-scale streaming buffers.

Every frame and batch buffer in the infeed path is megabytes — far above
glibc's default 128 KB mmap threshold, so malloc serves each one with a
fresh mmap and frees it with munmap. The hidden cost is not the syscall
but the PAGE FAULTS: every reallocated buffer is re-faulted (and
kernel-zeroed) page by page on first touch, which costs more than the
actual memcpy through it on the streaming path.

``enable_large_alloc_reuse()`` raises the mmap threshold so MB-scale
blocks come from the regular heap and get REUSED across frames/batches —
one fault per page for the process lifetime instead of per allocation.
Call it once at process start (producer CLIs and consumers do);
it is a no-op on non-glibc platforms.
"""

from __future__ import annotations

import ctypes
import logging

logger = logging.getLogger(__name__)

_M_TRIM_THRESHOLD = -1
_M_MMAP_THRESHOLD = -3


def enable_large_alloc_reuse(threshold_bytes: int = 1 << 28) -> bool:
    """Raise glibc's malloc mmap AND trim thresholds (default: 256 MB).

    Both knobs matter: the mmap threshold keeps MB-scale allocations on
    the heap, and the trim threshold keeps MB-scale FREES at the top of
    the heap from being returned to the kernel (``systrim``) — without it
    a freed batch buffer adjacent to the heap top is unmapped anyway and
    the next allocation re-faults every page, the exact cost this exists
    to eliminate. Returns True when applied, False when unavailable
    (non-glibc libc)."""
    try:
        libc = ctypes.CDLL("libc.so.6", use_errno=True)
        ok_mmap = bool(libc.mallopt(_M_MMAP_THRESHOLD, int(threshold_bytes)))
        ok_trim = bool(libc.mallopt(_M_TRIM_THRESHOLD, int(threshold_bytes)))
        if not (ok_mmap and ok_trim):
            logger.debug("mallopt rejected (mmap=%s trim=%s)", ok_mmap, ok_trim)
        return ok_mmap and ok_trim
    except OSError:
        return False
