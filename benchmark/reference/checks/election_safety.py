"""No two successful records of ``elect_op`` share a key with different
args: one winner a term (key = term, arg = winner), or one value a log
index (key = index, arg = the committed value)."""

from reference.history import OK_OK


def violated(hist, elect_op: int) -> bool:
    first: dict = {}  # key -> arg of its first successful record
    for op, key, arg, _client, ok in hist:
        if op == elect_op and ok == OK_OK:
            if first.setdefault(key, arg) != arg:
                return True
    return False
