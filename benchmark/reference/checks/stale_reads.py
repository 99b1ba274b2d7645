"""A successful read returns at least the newest version whose write, by
any client, had completed before the read was invoked."""

from reference.history import _floor_violated


def violated(hist) -> bool:
    return _floor_violated(hist, own_writes_only=False)
