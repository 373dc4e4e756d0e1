"""Faults planted in the program under a cell's timed path, one module per
entry (``bench/tests/faults/<entry>.py``), found by the entry's name.  Each
module lists its faults in ``FAULTS``; each fault takes a ``monkeypatch``
(or none, in a child process, to plant it for good) and breaks one thing
the comparison with the reference has to catch."""

import importlib
import importlib.util


class _ForGood:
    @staticmethod
    def setattr(target, name, value):
        setattr(target, name, value)


def _patch(mp, target, name, wrap):
    (mp or _ForGood).setattr(target, name, wrap(getattr(target, name)))


def of(entry: str):
    """The fault module of ``entry``, or None where it has none."""
    name = f"{__name__}.{entry}"
    if importlib.util.find_spec(name) is None:
        return None
    return importlib.import_module(name)


def plant(entry: str, fault: str, mp=None) -> None:
    """Plant the fault ``fault`` of ``entry``'s module."""
    getattr(of(entry), fault)(mp)
