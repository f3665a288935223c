"""Room for a thread's Python frames, so that no call site sits on the edge
of a frame chunk.

CPython (3.11 and later) keeps a thread's frames in chunks of 16 KiB and
gives a chunk back to the system the moment the first frame in it returns.
A call site whose callee's frame happens to be the first of a new chunk
therefore pays an ``mmap`` and a ``munmap`` PER CALL: measured on this
installation, 6.5 us for a call that takes 35 ns one frame higher or lower.
Which site that is depends on the sum of the frame sizes above it, so any
edit of a function on the way from the thread's start to a hot loop moves
it: with the step record of PR 37 (a few more locals in ``_launch``) it
landed inside Mosaic's lowering of ``kda_chunk``'s loop body, and every
first use of a program with a segment part took 3.5 s longer (PERF.md
section 6, PR 37).

A frame that needs more than a chunk gets a chunk of its own size, and
every deeper frame then lives inside that one chunk: ``roomy_stack`` runs a
thread's main function under such a frame.
"""

from __future__ import annotations

import functools
import types

# Words of value stack the roomy frame claims (it uses three): with 8-byte
# words a frame of 512 KiB in a chunk of 1 MiB, so 512 KiB of frames (a
# few thousand) fit below it before the next chunk's edge; pages nobody
# touches are never made resident.
ROOMY_WORDS = 1 << 16


def _call(fn, args, kwargs):
    return fn(*args, **kwargs)


_call_roomy = types.FunctionType(
    _call.__code__.replace(co_stacksize=ROOMY_WORDS), globals(),
    "_call_roomy")


def roomy_stack(fn):
    """Decorator: ``fn`` runs under one frame so large that the frames
    below it share one chunk (module docstring). For the main function of
    a long-lived thread; costs one call."""
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        return _call_roomy(fn, args, kwargs)
    return wrapper
