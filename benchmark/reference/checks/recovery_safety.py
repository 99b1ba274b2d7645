"""A restarted node never comes back below what it last synced: a
successful ``recover_op`` record's arg is never below the arg of the same
client's latest earlier successful ``sync_op`` record (the latest, not
the largest: a legitimate truncation syncs a shorter log)."""

from reference.history import OK_OK


def violated(hist, sync_op: int, recover_op: int) -> bool:
    synced: dict = {}  # client -> arg of its latest sync so far
    for op, _key, arg, client, ok in hist:
        if ok != OK_OK:
            continue
        if op == sync_op:
            synced[client] = arg
        elif op == recover_op and client in synced and arg < synced[client]:
            return True
    return False
