"""Host crossings: the one way the program moves data across the host
boundary on purpose.

``HostBoundary.pull`` (device -> host) and ``HostBoundary.push`` (host ->
device) wrap a crossing the code already makes.  Each one

  * spans it on the tracer (``d2h`` / ``h2d``, attributes ``site`` and
    ``bytes``), so a profiler trace names the gap it leaves on the device;
  * records its bytes in the ``TransferLedger`` under a cause, stage and
    column;
  * counts a pull as one host sync, ``host_syncs{site=...}`` in the
    metrics registry (a pull waits for the device to finish the value).

Neither adds a sync, a ``block_until_ready`` or a copy: ``pull`` is
``jax.device_get`` leaf by leaf, as the call sites always pulled, and
``push`` calls the placement the caller already used.  A pull with
``cause=None`` is a control scalar (a match count, a row count): a
sync, counted and spanned, with no bytes in the ledger.
A boundary with no ledger or registry records nothing there, so a
standalone ``CoProcessor`` pays a branch per crossing.  A caller that
reports its own total of the bytes it moved (``QueryOutcome``'s
``host_bytes_moved``) passes a ``Tally``: the boundary adds to it the
bytes it recorded, so the two figures cannot drift apart.
"""
from __future__ import annotations

import jax
import numpy as np

from .trace import NULL_TRACER


def host_nbytes(tree) -> int:
    """Bytes of the host (NumPy) leaves of ``tree``."""
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree)
               if isinstance(x, (np.ndarray, np.generic)))


class Tally:
    """Bytes a caller's crossings moved, added by ``HostBoundary``."""

    __slots__ = ("bytes",)

    def __init__(self) -> None:
        self.bytes = 0


class HostBoundary:
    """Spans, ledgers and counts host crossings for one owner (the
    service, or a ``CoProcessor`` the service adopted)."""

    def __init__(self, tracer=NULL_TRACER, ledger=None, metrics=None):
        self.tracer = tracer
        self.ledger = ledger
        self.metrics = metrics

    def pull(self, x, *, site: str, cause: str | None, stage: str = "",
             column: str = "-", tenant: str = "default",
             tally: Tally | None = None):
        """``jax.device_get`` of each leaf of ``x``, recorded.  ``stage``
        defaults to ``site``."""
        with self.tracer.span("d2h", site=site) as sp:
            out = jax.tree.map(jax.device_get, x)
            nbytes = host_nbytes(out)
            if sp is not None:
                sp.set(bytes=nbytes)
        if self.metrics is not None:
            self.metrics.inc("host_syncs", site=site)
        if cause is not None and self.ledger is not None:
            self.ledger.record(nbytes, cause=cause, stage=stage or site,
                               column=column, direction="d2h",
                               tenant=tenant)
        if tally is not None:
            tally.bytes += nbytes
        return out

    def push(self, x, place, *, site: str, cause: str,
             stage: str = "", column: str = "-", tenant: str = "default",
             tally: Tally | None = None):
        """``place(x)``, recorded: the bytes are those of ``x``'s host
        leaves (device leaves in ``x`` do not cross)."""
        nbytes = host_nbytes(x)
        with self.tracer.span("h2d", site=site, bytes=nbytes):
            out = place(x)
        if self.ledger is not None:
            self.ledger.record(nbytes, cause=cause, stage=stage or site,
                               column=column, direction="h2d",
                               tenant=tenant)
        if tally is not None:
            tally.bytes += nbytes
        return out
