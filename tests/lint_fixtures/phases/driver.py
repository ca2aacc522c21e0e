"""Fixture driver exercising BGT020/BGT021 against phases.py's catalog."""

import types


class _T:
    def phase(self, name):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


# the module-level timer of the ticking set (a pull site below the driver)
phases = types.SimpleNamespace(phase=lambda name: _T())


def run(t: _T, dynamic: str):
    with t.phase("inputs"):
        pass
    with t.phase("advance"):
        with phases.phase("blocked"):  # nested self-time phase: clean
            pass
    with t.phase("typo_phase"):      # BGT020: not in the catalog
        pass
    with t.phase(dynamic):           # BGT020: not one string literal
        pass
    t.phase("checksum")              # BGT021: bare call, times nothing
