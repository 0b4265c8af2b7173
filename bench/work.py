"""Work functions: the bytes a kernel's decisions need, counted from the
deployment's shapes alone.

The count never looks at how a kernel implements the work - not at the
n+1 prefix replicas of the decision batch, not at lanes padded to 128 -
so it stays the same whatever implements the kernel, and a kernel's
share of its roofline can only rise by doing less than it does today.
No int32 vector peak of the v5e is published, so the roofline is the
HBM bound alone: least time = bytes / HBM bytes per second.
"""

from __future__ import annotations

INT32 = 4


def mesi_tick_bytes(n: int, m: int) -> int:
    """One coherence tick over one directory of ``n`` agents x ``m``
    artifacts: read and write back the MESI state and the agents' synced
    versions (n x m each) and the authority versions (m); read one
    request per agent (act, artifact, write) and write one answer per
    agent (fill bit, served version)."""
    directory = 2 * n * m + m
    return INT32 * (2 * directory + 3 * n + 2 * n)


def chunk_tick_bytes(n: int, m: int, c: int) -> int:
    """One content-plane tick: read and write back the chunk versions
    and dirty bits (m x c each) and the readers' chunk vectors
    (n x m x c); read each agent's fill bit, artifact, write bit and
    write span (n x c); write the chunks each fill ships (n x c)."""
    state = 2 * m * c + n * m * c
    return INT32 * (2 * state + 3 * n + 2 * n * c)
