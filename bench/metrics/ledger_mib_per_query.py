"""Host-boundary bytes the TransferLedger recorded in the window, all
causes (fingerprint, multicol_pack, handoff, result), per completed
query, in MiB.  Base-table uploads are not among its causes."""
UNIT = "MiB"


def read(r):
    if not r.completed:
        return None
    return r.ledger_bytes / float(1 << 20) / r.completed
